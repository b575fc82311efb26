"""Boolean and top-k queries over the compressed inverted index.

Every query is a decode→intersect→score pipeline over the kernel stack —
posting lists are never materialized as whole docid arrays unless they ARE
the answer (a union's output):

* **Conjunctive (AND)** — terms ordered by document frequency; the rarest
  term is the *driver* and only its blocks inside the terms' common docid
  window are decoded (``stream`` epilogue). Its docids become the probe
  set, processed in fixed-width chunks: for every other term, each probe
  binary-searches the skip table (``first_doc``/``last_doc``) and only
  the blocks whose docid range actually contains a probe are gathered —
  per chunk that is ≤ ``probe_width`` blocks out of the whole list, and
  every other block is **never decoded**. The ``membership`` epilogue
  decodes the gathered blocks and emits the chunk's match bitmap
  in-kernel — the larger list's docids live and die in VMEM. This is
  small-vs-large galloping intersection with the gallop done on the skip
  table and the per-tile comparison vectorized on the VPU.
* **Disjunctive (OR)** — the union is the output, so each term's live
  blocks are decoded once (no probes to prune against) and merged.
* **Top-k** — disjunctive top-k (the default) scores term-at-a-time: the
  union pass already decodes every term's docids, so each term's
  quantized impact scatters straight onto them (TAAT — no re-decode;
  per-posting impact streams decode alongside when the index carries
  tfs). Conjunctive top-k (``mode="and"``) probes each term's impact
  per candidate (constant-score shortcut when tf-free). Required-term
  top-k (``mode="driver"``) is the scored DAAT shape: candidates are
  ``terms[0]``'s postings, and each optional term's impact accumulates
  per candidate chunk through the fused ``bm25_accum``/``bm25_accum_rows``
  (or per-posting ``bm25_weighted``/``bm25_weighted_rows``) epilogues with
  the same skip-table pruning as AND.
* **MaxScore (``mode="maxscore"``)** — block-max dynamic-pruned
  disjunctive top-k, same results as ``mode="or"`` bit-exactly. Terms
  sort ascending by their score upper bound (``TermPostings.ub``, the
  largest per-block ``max_impact``); once the running top-k holds k
  results its k-th score is the threshold θ, and the maximal prefix of
  terms whose cumulative upper bound ≤ θ becomes **non-essential**: those
  lists are never strip-decoded, only probed for candidates that can
  still pass. The remaining **essential** terms advance DAAT in docid
  strips of ≤ ``probe_width`` postings per term; inside a strip, any
  block whose ``max_impact`` plus the other terms' upper bounds < θ is
  **pruned — never decoded** (its docs can't even tie an incumbent).
  Every bound comparison is *strict*: a candidate whose best case ties θ
  must still be scored, because the final (score desc, docid asc) order
  ranks it ahead of any incumbent tied at θ with a larger docid — the
  seed phase inserts incumbents at arbitrary docids, so tied candidates
  with smaller docids do occur. Candidates surviving the
  partial-score bound are probed against non-essential terms in
  descending-bound order, re-checking the bound after each term
  (``QueryStats.probes_pruned`` counts settlements without decode).

Impacts are exact int32, so fused / unfused / sharded / Pallas / jnp
runs are bit-identical; ties break by ascending docid. ``plan=`` is
forwarded to the dispatch layer, so queries inherit its default route
per format (``dispatch.default_plan``), both Pallas/jnp paths, Stream
VByte's dense and banded cores — and, when a term's
``CompressedIntArray`` is block-sharded over a mesh (``use_skip=False``
resident-index mode, see ``launch.serve.SearchEngine``), the ``shard_map``
block-parallel path. :class:`QueryStats` counts decoded vs skipped vs
threshold-pruned blocks, which is how tests prove pruning never decodes
non-overlapping — or beaten — blocks.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import numpy as np

import jax.numpy as jnp

from repro.kernels.vbyte_decode import dispatch
from repro.kernels.vbyte_decode.ops import normalize_probe
from repro.obs import trace as _trace
from repro.robustness.validate import Deadline  # noqa: F401  (re-exported)

from .builder import InvertedIndex, TermPostings

# maximum probe-set width per membership/scoring pass. Chunks are sized
# min(pow2(candidates), this), so a rare driver probes each term in ONE
# dispatch; the cap bounds the [tile, B, P] comparison footprint (and the
# jitted shape count — pow2 widths only).
DEFAULT_PROBE_WIDTH = 512
# MaxScore strip ramp: ×8 per round, capped. One small first strip forms
# θ cheaply; after that per-dispatch overhead dwarfs per-block decode
# cost, so the horizon grows fast — a 4096-block list takes ~4 strips,
# not ~1000. The cap bounds one pull's decode shape (2048 blocks × 128
# ints = 256Ki ints).
STRIP_RAMP = 8
MAX_STRIP_BLOCKS = 2048
# MaxScore candidate-scoring crossover: at or below this many candidates
# a term is probed through the row-gathered weighted epilogues (O(B) per
# probe, probe set in VMEM); above it, bulk decode-and-merge — the probe
# epilogues pay per gathered row, so strip-sized candidate sets would
# cost more than decoding every hit block exactly once.
MERGE_MIN_PROBES = 32


@dataclass
class QueryStats:
    """Decode accounting for one query (pruning evidence).

    ``blocks_decoded + blocks_skipped`` equals the blocks *considered* by
    skip-table routing (per decode/probe pass); ``rows_gathered`` counts
    per-probe row gathers on top (a block gathered once per probe in it —
    the real decode work of the row-aligned probe path, which is why
    ``ints_decoded`` follows rows, not unique blocks). ``blocks_pruned`` /
    ``postings_pruned`` count whole blocks (and the postings inside them)
    eliminated by the MaxScore threshold — **never decoded by any pass**:
    a block gathered by a non-essential probe/merge pass is excluded even
    if the strip cursor never reached it, so per term
    ``per_term_pruned[t] + len(per_term_blocks[t]) == n_blocks(t)`` is an
    exact disjoint partition (``per_term_blocks`` is the set of live block
    rows decoded at least once). ``probes_pruned`` counts
    (candidate × term) probes settled by the score bound alone.
    ``impact_ints_decoded`` counts per-posting impact integers decoded
    from the weight streams (MaxScore / tf-scored paths).
    """

    blocks_decoded: int = 0
    blocks_skipped: int = 0
    blocks_pruned: int = 0  # MaxScore threshold-pruned, never decoded
    rows_gathered: int = 0  # per-probe row gathers (duplicates included)
    ints_decoded: int = 0  # valid integers in decoded blocks/rows
    impact_ints_decoded: int = 0  # per-posting impacts decoded alongside
    postings_pruned: int = 0  # postings inside threshold-pruned blocks
    probes_pruned: int = 0  # candidate×term probes settled by bound alone
    decode_calls: int = 0
    per_term_decoded: dict = field(default_factory=dict)
    per_term_pruned: dict = field(default_factory=dict)
    per_term_blocks: dict = field(default_factory=dict)  # term -> set of
    #   live block rows decoded at least once (strip-pulled or gathered)
    # robustness accounting (docs/robustness.md): a degraded result is
    # still correct over the work that ran — smaller, never silently wrong
    errors: int = 0  # typed DecodeErrors hit while answering
    retries: int = 0  # transient-failure retries that succeeded
    quarantined_blocks: int = 0  # blocks of quarantined segments not served
    bound_fallbacks: int = 0  # maxscore→TAAT fallbacks (unsafe bounds)
    # live-index accounting (repro.index.ingest): postings served from the
    # uncompressed delta layer, result docs sourced from it, and main-
    # segment postings suppressed by the tombstone set at query time
    delta_postings: int = 0
    delta_hits: int = 0
    tombstones_applied: int = 0
    degraded: bool = False
    degraded_reasons: list = field(default_factory=list)

    def mark_degraded(self, reason: str):
        self.degraded = True
        if reason not in self.degraded_reasons:
            self.degraded_reasons.append(reason)

    def merge(self, other: "QueryStats"):
        """Fold a per-query stats object into this aggregate — how
        ``SearchEngine``/``run_workload`` keep one per-call degraded flag
        while still reporting workload-wide decode accounting.

        Iterates ``dataclasses.fields`` so a newly added counter merges by
        its type instead of being silently dropped; a field with no merge
        rule (unsupported type) raises at the first merge, which is the
        test-enforced contract for extending this class.
        """
        for f in dataclasses.fields(self):
            mine = getattr(self, f.name)
            theirs = getattr(other, f.name)
            if isinstance(mine, bool):
                setattr(self, f.name, mine or theirs)
            elif isinstance(mine, (int, float)):
                setattr(self, f.name, mine + theirs)
            elif isinstance(mine, dict):
                for t, v in theirs.items():
                    if isinstance(v, (set, frozenset)):
                        mine.setdefault(t, set()).update(v)
                    elif isinstance(v, (int, float)):
                        mine[t] = mine.get(t, 0) + v
                    else:
                        raise TypeError(
                            f"QueryStats.merge: no merge rule for dict "
                            f"field {f.name!r} value of type "
                            f"{type(v).__name__}")
            elif isinstance(mine, list):
                for r in theirs:  # dedup-append (degraded_reasons order)
                    if r not in mine:
                        mine.append(r)
            elif isinstance(mine, set):
                mine.update(theirs)
            else:
                raise TypeError(
                    f"QueryStats.merge: no merge rule for field "
                    f"{f.name!r} of type {type(mine).__name__} — add one "
                    f"here before adding the field")

    def span_attrs(self) -> dict:
        """Flat attribute dict for trace spans: every scalar counter plus
        the degraded flag/reasons (the per-term dicts summarize as sizes —
        span attributes stay JSON-scalar-ish; the dataclass remains the
        full-fidelity API)."""
        out = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, (bool, int, float)):
                out[f.name] = v
            elif isinstance(v, dict):
                out[f"{f.name}_terms"] = len(v)
            elif isinstance(v, list):
                out[f.name] = list(v)
        return out

    def count(self, term: int, decoded: int, skipped: int, ints: int):
        self.blocks_decoded += decoded
        self.blocks_skipped += skipped
        self.ints_decoded += ints
        self.decode_calls += 1
        self.per_term_decoded[term] = (
            self.per_term_decoded.get(term, 0) + decoded)

    def count_pruned(self, blocks: int, postings: int, term=None):
        self.blocks_pruned += blocks
        self.postings_pruned += postings
        if term is not None:
            self.per_term_pruned[term] = (
                self.per_term_pruned.get(term, 0) + blocks)

    def touch(self, term: int, rows):
        """Record live block rows of ``term`` decoded at least once."""
        self.per_term_blocks.setdefault(term, set()).update(
            int(r) for r in rows)


def _pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


def _expired(deadline: Deadline | None, stats: QueryStats | None,
             where: str) -> bool:
    """Deadline check at a work-unit boundary (docs/robustness.md).

    Work in flight always completes — expiry only stops *new* strips /
    terms / chunks from starting, so a timed-out query returns a smaller
    but well-defined result, flagged via ``stats.degraded``.
    """
    if deadline is None or not deadline.expired():
        return False
    if stats is not None:
        stats.mark_degraded(f"deadline:{where}")
    return True


def _overlap_blocks(tp: TermPostings, lo: int, hi: int) -> tuple[int, int]:
    """Block range ``[i0, i1)`` whose ``[first, last]`` intersects [lo, hi].

    ``first_doc``/``last_doc`` are sorted (postings are), so this is two
    binary searches — the skip-table gallop.
    """
    i0 = int(np.searchsorted(tp.last_doc, lo, side="left"))
    i1 = int(np.searchsorted(tp.first_doc, hi, side="right"))
    return i0, max(i1, i0)


def _decode_blocks(tp: TermPostings, i0: int, i1: int, *, plan, stats,
                   use_skip: bool) -> np.ndarray:
    """Decode blocks ``[i0, i1)`` of one term to sorted uint32 docids."""
    if not use_skip:
        i0, i1 = 0, tp.n_blocks
    if i1 <= i0:
        return np.zeros(0, np.uint32)
    if use_skip and (i0, i1) != (0, tp.n_blocks):
        sub = tp.arr.slice_blocks(i0, i1, pad_to=_pow2(i1 - i0))
    else:
        # whole list: decode the resident (possibly sharded) array in
        # place — slicing would just copy and re-upload every leaf
        sub = tp.arr
    if stats is not None:
        stats.count(tp.term, i1 - i0, tp.n_blocks - (i1 - i0), sub.n)
        stats.touch(tp.term, range(i0, i1))
    return sub.decode(plan=plan)


def _decode_impact_stream(tp: TermPostings, *, plan, stats) -> np.ndarray:
    """Decode the whole per-posting impact stream, aligned with the docid
    list (identical block layout — see builder)."""
    if stats is not None:
        stats.impact_ints_decoded += tp.impacts.n
        stats.decode_calls += 1
    return tp.impacts.decode(plan=plan).astype(np.int64)


def _weight_extras(weights, rows=None, *, pad=None):
    """Format-tagged weight operands for the ``bm25_weighted*`` epilogues,
    optionally row-gathered to align with a gathered main stream."""
    sub = weights if rows is None else weights.take_blocks(rows, pad_to=pad)
    ops = sub.device_operands()
    extras = {f"w_{k}": v for k, v in ops.items()
              if k in ("payload", "control", "data", "widths")}
    return extras, sub.n


def _route_probes(tp: TermPostings, chunk: np.ndarray):
    """Per-probe skip-table gallop: ``(ok mask, block id per hit probe)``.

    Each probe binary-searches ``first_doc``/``last_doc``; a probe that
    lands between two blocks' docid ranges is in no block at all and is
    settled without decoding anything. The hit probes each name the single
    block that can contain them.
    """
    pos = np.searchsorted(tp.first_doc, chunk, side="right") - 1
    ok = pos >= 0
    ok &= chunk <= tp.last_doc[np.maximum(pos, 0)]
    return ok, pos[ok]


def _probe_pass(tp: TermPostings, chunk: np.ndarray, *, impact: int,
                probe_width: int, plan, stats, use_skip: bool,
                weights=None, touched=None) -> np.ndarray:
    """Skip-gallop stage span around :func:`_probe_pass_impl`."""
    with _trace("gallop", term=tp.term, probes=len(chunk)) as sp:
        if sp and stats is not None:
            b0, i0 = stats.blocks_decoded, stats.ints_decoded
        out = _probe_pass_impl(tp, chunk, impact=impact,
                               probe_width=probe_width, plan=plan,
                               stats=stats, use_skip=use_skip,
                               weights=weights, touched=touched)
        if sp and stats is not None:
            sp.set(blocks_decoded=stats.blocks_decoded - b0,
                   ints_decoded=stats.ints_decoded - i0)
        return out


def _probe_pass_impl(tp: TermPostings, chunk: np.ndarray, *, impact: int,
                     probe_width: int, plan, stats, use_skip: bool,
                     weights=None, touched=None) -> np.ndarray:
    """One (term, candidate-chunk) pass: int32 [len(chunk)] per-candidate
    result — the membership bitmap (``impact=0``), the constant bm25
    impact contribution (``impact>0``), or the exact per-posting impact
    contribution (``weights=`` the term's impact ``CompressedIntArray``,
    decoded in the same tile pass by the ``bm25_weighted*`` epilogues).

    With skip pruning, each hit probe gathers its one candidate block and
    the block-aligned ``*_rows`` epilogue compares probe t against tile t
    only (O(B) per probe). Without (resident/sharded arrays), the whole
    list decodes under the broadcast epilogue with the probe set in VMEM.
    """
    if use_skip:
        ok, rows = _route_probes(tp, chunk)
        if rows.size == 0:  # every probe galloped past: nothing decoded
            if stats is not None:
                stats.count(tp.term, 0, tp.n_blocks, 0)
            return np.zeros(len(chunk), np.int32)
        uniq = np.unique(rows)
        if touched is not None:
            touched.update(uniq.tolist())
        if stats is not None:
            stats.touch(tp.term, uniq)
        res = np.zeros(len(chunk), np.int32)
        if uniq.size * 2 > rows.size:
            # mostly-distinct blocks: one gathered row per probe, O(B)
            # compare against its own tile. decoded+skipped covers the
            # blocks considered exactly once; the per-probe duplicates are
            # rows_gathered (ints follow rows — the real decode work).
            row_ints = int(np.asarray(tp.arr.counts)[rows].sum())
            if stats is not None:
                stats.count(tp.term, int(uniq.size),
                            tp.n_blocks - int(uniq.size), row_ints)
                stats.rows_gathered += int(rows.size)
            pad = _pow2(rows.size)
            sub = tp.arr.take_blocks(rows, pad_to=pad)
            probe = np.full((pad, 1), -1, np.int32)
            probe[: rows.size, 0] = chunk[ok].astype(np.int32)
            extras = {"probe": jnp.asarray(probe)}
            if weights is not None:
                w_extras, w_ints = _weight_extras(weights, rows, pad=pad)
                extras.update(w_extras)
                if stats is not None:
                    stats.impact_ints_decoded += w_ints
                ep_name = "bm25_weighted_rows"
            elif impact:
                extras["impact"] = jnp.asarray([[impact]], jnp.int32)
                ep_name = "bm25_accum_rows"
            else:
                ep_name = "membership_rows"
            out = dispatch.decode(sub, epilogue=ep_name,
                                  epilogue_operands=extras, plan=plan)
            res[ok] = np.asarray(out)[: rows.size, 0]
            return res
        # probes pile into few blocks (short lists): duplicating rows
        # would re-decode each block once per probe — gather each hit
        # block ONCE and run the broadcast epilogue over the chunk
        if stats is not None:
            stats.count(tp.term, int(uniq.size),
                        tp.n_blocks - int(uniq.size),
                        int(np.asarray(tp.arr.counts)[uniq].sum()))
        pad = _pow2(uniq.size)
        sub = tp.arr.take_blocks(uniq, pad_to=pad)
        w = _pow2(len(chunk))
        extras = {"probe": jnp.asarray(normalize_probe(chunk, w))}
        if weights is not None:
            w_extras, w_ints = _weight_extras(weights, uniq, pad=pad)
            extras.update(w_extras)
            if stats is not None:
                stats.impact_ints_decoded += w_ints
            ep_name = "bm25_weighted"
        elif impact:
            extras["impact"] = jnp.asarray([[impact]], jnp.int32)
            ep_name = "bm25_accum"
        else:
            ep_name = "membership"
        out = dispatch.decode(sub, epilogue=ep_name,
                              epilogue_operands=extras, plan=plan)
        res[:] = np.asarray(out).sum(axis=0, dtype=np.int32)[: len(chunk)]
        return res
    sub = tp.arr
    if touched is not None:
        touched.update(range(tp.n_blocks))
    if stats is not None:
        stats.count(tp.term, tp.n_blocks, 0, sub.n)
        stats.touch(tp.term, range(tp.n_blocks))
    extras = {"probe": jnp.asarray(normalize_probe(chunk, probe_width))}
    if weights is not None:
        w_extras, w_ints = _weight_extras(weights)
        extras.update(w_extras)
        if stats is not None:
            stats.impact_ints_decoded += w_ints
        ep_name = "bm25_weighted"
    elif impact:
        extras["impact"] = jnp.asarray([[impact]], jnp.int32)
        ep_name = "bm25_accum"
    else:
        ep_name = "membership"
    out = dispatch.decode(sub, epilogue=ep_name,
                          epilogue_operands=extras, plan=plan)
    # a docid lives in exactly one block → summing blocks is exact int32
    return np.asarray(out).sum(axis=0, dtype=np.int32)[: len(chunk)]


def _merge_pass(tp: TermPostings, chunk: np.ndarray, *, impact: int,
                plan, stats, weights=None, touched=None) -> np.ndarray:
    """Merge stage span around :func:`_merge_pass_impl`."""
    with _trace("merge", term=tp.term, candidates=len(chunk)) as sp:
        if sp and stats is not None:
            b0, i0 = stats.blocks_decoded, stats.ints_decoded
        out = _merge_pass_impl(tp, chunk, impact=impact, plan=plan,
                               stats=stats, weights=weights,
                               touched=touched)
        if sp and stats is not None:
            sp.set(blocks_decoded=stats.blocks_decoded - b0,
                   ints_decoded=stats.ints_decoded - i0)
        return out


def _merge_pass_impl(tp: TermPostings, chunk: np.ndarray, *, impact: int,
                     plan, stats, weights=None, touched=None) -> np.ndarray:
    """Bulk variant of :func:`_probe_pass` for candidate sets too large to
    probe: int64 [len(chunk)] per-candidate contribution.

    The probe epilogues pay per probe — a strip's worth of MaxScore
    candidates against a long non-essential list would gather (and decode)
    one row per candidate, re-decoding hot blocks hundreds of times across
    dozens of chunked dispatches. Here each block that contains any
    candidate decodes exactly once (a single gathered dispatch per stream)
    and the membership test is a host-side ``searchsorted`` merge —
    gathered blocks ascend, so their concatenated postings stay sorted.
    """
    res = np.zeros(len(chunk), np.int64)
    ok, rows = _route_probes(tp, chunk)
    if rows.size == 0:
        if stats is not None:
            stats.count(tp.term, 0, tp.n_blocks, 0)
        return res
    uniq = np.unique(rows)
    if touched is not None:
        touched.update(uniq.tolist())
    if stats is not None:
        stats.touch(tp.term, uniq)
    pad = _pow2(uniq.size)
    if uniq.size == uniq[-1] - uniq[0] + 1:
        sub = tp.arr.slice_blocks(uniq[0], uniq[-1] + 1, pad_to=pad)
        wsub = (weights.slice_blocks(uniq[0], uniq[-1] + 1, pad_to=pad)
                if weights is not None else None)
    else:
        sub = tp.arr.take_blocks(uniq, pad_to=pad)
        wsub = (weights.take_blocks(uniq, pad_to=pad)
                if weights is not None else None)
    if stats is not None:
        stats.count(tp.term, int(uniq.size),
                    tp.n_blocks - int(uniq.size), sub.n)
    docs = sub.decode(plan=plan)
    if wsub is not None:
        if stats is not None:
            stats.impact_ints_decoded += wsub.n
            stats.decode_calls += 1
        imps = wsub.decode(plan=plan).astype(np.int64)
    else:
        imps = np.full(docs.size, impact, np.int64)
    pos = np.searchsorted(docs, chunk[ok])
    pos = np.minimum(pos, docs.size - 1)
    hit = docs[pos] == chunk[ok]
    vals = np.where(hit, imps[pos], 0)
    res[np.flatnonzero(ok)] = vals
    return res


def _score_term(tp: TermPostings, base_impact: int, cand: np.ndarray,
                sel: np.ndarray, scores: np.ndarray, *, has_tf: bool,
                probe_width: int, plan, stats, touched=None):
    """Add term ``tp``'s exact contribution to ``scores[sel]``: bulk
    decode-and-merge for strip-sized candidate sets, chunked probe
    epilogues for small ones (one dispatch per chunk, rows in VMEM).
    ``touched`` (a set) collects the block rows actually gathered, so
    MaxScore's exit accounting never books a probe-decoded block as
    threshold-pruned."""
    with _trace("score", term=tp.term, candidates=int(sel.size)) as sp:
        if sp and stats is not None:
            b0, i0 = stats.blocks_decoded, stats.ints_decoded
        _score_term_impl(tp, base_impact, cand, sel, scores, has_tf=has_tf,
                         probe_width=probe_width, plan=plan, stats=stats,
                         touched=touched)
        if sp and stats is not None:
            sp.set(blocks_decoded=stats.blocks_decoded - b0,
                   ints_decoded=stats.ints_decoded - i0)


def _score_term_impl(tp: TermPostings, base_impact: int, cand: np.ndarray,
                     sel: np.ndarray, scores: np.ndarray, *, has_tf: bool,
                     probe_width: int, plan, stats, touched=None):
    wts = tp.impacts if has_tf else None
    if sel.size > MERGE_MIN_PROBES:
        scores[sel] += _merge_pass(
            tp, cand[sel].astype(np.uint32), impact=base_impact,
            plan=plan, stats=stats, weights=wts, touched=touched)
        return
    w = min(_pow2(sel.size), probe_width)
    for s in range(0, sel.size, w):
        ch = sel[s:s + w]
        contrib = _probe_pass(
            tp, cand[ch].astype(np.uint32), impact=base_impact,
            probe_width=w, plan=plan, stats=stats, use_skip=True,
            weights=wts, touched=touched)
        scores[ch] += contrib.astype(np.int64)


def _term_postings(index: InvertedIndex, terms) -> list[TermPostings]:
    out = []
    for t in terms:
        tp = index.terms.get(t)
        out.append(tp if tp is not None
                   else TermPostings(term=t, arr=None,
                                     first_doc=np.zeros(0, np.uint32),
                                     last_doc=np.zeros(0, np.uint32), df=0))
    return out


def conjunctive(
    index: InvertedIndex,
    terms,
    *,
    plan="auto",
    probe_width: int = DEFAULT_PROBE_WIDTH,
    stats: QueryStats | None = None,
    use_skip: bool = True,
    deadline: Deadline | None = None,
) -> np.ndarray:
    """AND query: sorted uint32 docids present in every term's postings.

    On deadline expiry the remaining terms are skipped and the
    intersection-so-far is returned — a *superset* of the exact answer,
    flagged degraded via ``stats`` (docs/robustness.md).
    """
    if not terms:
        raise ValueError("conjunctive query needs ≥1 term")
    # dedup repeated terms: AND(t, t) = t, and each repeat would re-probe
    tps = sorted(_term_postings(index, dict.fromkeys(terms)),
                 key=lambda tp: tp.df)
    if tps[0].df == 0:
        return np.zeros(0, np.uint32)
    # common docid window: outside [lo, hi] no doc can be in all terms
    lo = max(int(tp.first_doc[0]) for tp in tps)
    hi = min(int(tp.last_doc[-1]) for tp in tps)
    if lo > hi:
        return np.zeros(0, np.uint32)
    driver, rest = tps[0], tps[1:]
    i0, i1 = _overlap_blocks(driver, lo, hi)
    cand = _decode_blocks(driver, i0, i1, plan=plan, stats=stats,
                          use_skip=use_skip)
    cand = cand[(cand >= lo) & (cand <= hi)]
    for tp in rest:
        if cand.size == 0:
            break
        if _expired(deadline, stats, "and-term"):
            break
        w = min(_pow2(cand.size), probe_width)
        keep = np.zeros(cand.size, bool)
        for s in range(0, cand.size, w):
            if s and _expired(deadline, stats, "and-chunk"):
                keep[s:] = True  # unprobed candidates stay (superset)
                break
            chunk = cand[s:s + w]
            hit = _probe_pass(tp, chunk, impact=0, probe_width=w, plan=plan,
                              stats=stats, use_skip=use_skip)
            keep[s:s + len(chunk)] = hit.astype(bool)
        cand = cand[keep]
    return cand.astype(np.uint32)


def disjunctive(
    index: InvertedIndex,
    terms,
    *,
    plan="auto",
    stats: QueryStats | None = None,
    use_skip: bool = True,
    deadline: Deadline | None = None,
) -> np.ndarray:
    """OR query: sorted uint32 docids present in any term's postings.

    On deadline expiry the remaining terms are skipped: the union-so-far
    (a subset) is returned, flagged degraded via ``stats``.
    """
    if not terms:
        raise ValueError("disjunctive query needs ≥1 term")
    parts = []
    for tp in _term_postings(index, dict.fromkeys(terms)):  # dedup repeats
        if tp.df == 0:
            continue
        if parts and _expired(deadline, stats, "or-term"):
            break
        parts.append(_decode_blocks(tp, 0, tp.n_blocks, plan=plan,
                                    stats=stats, use_skip=use_skip))
    if not parts:
        return np.zeros(0, np.uint32)
    return np.unique(np.concatenate(parts)).astype(np.uint32)


def _taat_scores(index: InvertedIndex, terms, *, plan, stats, use_skip,
                 deadline: Deadline | None = None):
    """Exhaustive TAAT scoring: every term decodes once (the union pass),
    its impacts scatter onto its own docids. ``(cand int64, scores int64)``,
    exact — the reference every pruned path must match bit-for-bit. On
    deadline expiry the remaining terms never decode: candidates and
    scores cover the terms that ran (flagged degraded via ``stats``)."""
    parts = {}
    for t in dict.fromkeys(terms):
        tp = index.terms.get(t)
        if tp is None or tp.df == 0:
            continue
        if parts and _expired(deadline, stats, "taat-term"):
            break
        parts[t] = _decode_blocks(tp, 0, tp.n_blocks, plan=plan,
                                  stats=stats, use_skip=use_skip)
    if not parts:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    with _trace("score", terms=len(parts)):
        cand = np.unique(
            np.concatenate(list(parts.values()))).astype(np.int64)
        scores = np.zeros(cand.size, np.int64)
        for t, docs in parts.items():
            tp = index.terms[t]
            if index.has_tf:
                # per-posting impacts: decode the aligned weight stream
                imps = _decode_impact_stream(tp, plan=plan, stats=stats)
                scores[np.searchsorted(cand, docs.astype(np.int64))] += imps
            else:
                scores[np.searchsorted(cand, docs.astype(np.int64))] \
                    += index.impact(t)
    return cand, scores


class _StripCursor:
    """Per-term DAAT cursor for MaxScore: advances block-aligned strips,
    buffering decoded postings beyond the strip boundary."""

    def __init__(self, tp: TermPostings, has_tf: bool, base_impact: int):
        self.tp = tp
        self.has_tf = has_tf
        self.base_impact = base_impact
        self.i = 0  # next undecoded block
        self.buf_docs = np.zeros(0, np.int64)
        self.buf_imps = np.zeros(0, np.int64)
        self.pruned_rows: list = []  # block rows dropped by θ at pull time
        #   (booked at exit, minus any later gathered by a probe pass)

    @property
    def exhausted(self) -> bool:
        return self.i >= self.tp.n_blocks and self.buf_docs.size == 0

    def pull(self, hi: int, theta: int | None, other_ub, *,
             plan, stats: QueryStats):
        """Decode this term's postings ≤ ``hi`` (buffer the overshoot).

        Advances over every block starting ≤ hi; with a threshold, any
        block whose ``max_impact + other_ub < θ`` is pruned — its postings
        can't even tie an incumbent — and never strip-decoded (the strict
        ``<`` keeps θ-tying blocks: a tied doc at a smaller docid outranks
        the incumbent under the final lexsort). ``other_ub`` is the other
        terms' score bound: a scalar, or a callable mapping the block rows
        under consideration to a per-row bound (MaxScore tightens it per
        block once seeded terms' docids are known). Pruned rows are only
        buffered here (``pruned_rows``); the exit accounting books them
        after subtracting any row a later probe pass gathered anyway.
        """
        tp = self.tp
        j = int(np.searchsorted(tp.first_doc, hi, side="right"))
        rows = np.arange(self.i, max(j, self.i))
        self.i = max(j, self.i)
        if theta is not None and rows.size:
            ou = other_ub(rows) if callable(other_ub) else other_ub
            beaten = (tp.max_impact[rows].astype(np.int64)
                      + ou < theta)
            if beaten.any():
                self.pruned_rows.append(rows[beaten])
                rows = rows[~beaten]
        if rows.size:
            pad = _pow2(rows.size)
            contiguous = rows.size == rows[-1] - rows[0] + 1
            if contiguous:
                sub = tp.arr.slice_blocks(rows[0], rows[-1] + 1, pad_to=pad)
                wsub = tp.impacts.slice_blocks(rows[0], rows[-1] + 1,
                                               pad_to=pad)
            else:
                sub = tp.arr.take_blocks(rows, pad_to=pad)
                wsub = tp.impacts.take_blocks(rows, pad_to=pad)
            stats.count(tp.term, int(rows.size), 0, sub.n)
            stats.touch(tp.term, rows)
            docs = sub.decode(plan=plan).astype(np.int64)
            if self.has_tf:
                stats.impact_ints_decoded += wsub.n
                stats.decode_calls += 1
                imps = wsub.decode(plan=plan).astype(np.int64)
            else:  # tf-free: the stream would decode to this constant
                imps = np.full(docs.size, self.base_impact, np.int64)
            docs = np.concatenate([self.buf_docs, docs])
            imps = np.concatenate([self.buf_imps, imps])
        else:
            docs, imps = self.buf_docs, self.buf_imps
        cut = int(np.searchsorted(docs, hi, side="right"))
        self.buf_docs, self.buf_imps = docs[cut:], imps[cut:]
        return docs[:cut], imps[:cut]


def _seeded_bound(c, total_ub: int, seed_docs):
    """Per-row bound on the OTHER terms' contribution to cursor ``c``'s
    blocks. Seeded terms are fully decoded, so a block containing none of
    a seeded term's docids provably gets zero from it — subtracting those
    ubs is what lets θ prune essential blocks even when the global
    ``Σ other ubs`` (dominated by a rare term's saturated impact) never
    drops below θ."""
    loose = total_ub - c.tp.ub

    def bound(rows: np.ndarray) -> np.ndarray:
        ou = np.full(rows.size, loose, np.int64)
        f = c.tp.first_doc[rows]
        l = c.tp.last_doc[rows]
        for s, ds in seed_docs:
            if s is c:
                continue
            absent = (np.searchsorted(ds, l, side="right")
                      == np.searchsorted(ds, f, side="left"))
            ou -= s.tp.ub * absent
        return ou

    return bound


def _maxscore(index: InvertedIndex, terms, k: int, *, plan, probe_width,
              stats: QueryStats | None, deadline: Deadline | None = None):
    """Block-max pruned disjunctive top-k (see module docstring).

    Bit-exact with :func:`_taat_scores` + lexsort by construction: every
    pruning decision only ever discards work whose best case is *strictly
    below* the current k-th score θ. Strictness matters: the seed phase
    puts exactly-scored incumbents at arbitrary docids into the heap, so
    a later candidate whose score ties θ at a smaller docid must still be
    generated — the final lexsort ranks it ahead of the tied incumbent."""
    st = stats if stats is not None else QueryStats()
    tps = [tp for tp in _term_postings(index, dict.fromkeys(terms))
           if tp.df > 0]
    if not tps:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    for tp in tps:
        if tp.impacts is None or tp.max_impact.size != tp.n_blocks:
            raise ValueError(
                "mode='maxscore' needs per-posting impact streams and the "
                "max_impact skip column — rebuild the index with "
                "build_index (optionally passing tfs=)")
    tps.sort(key=lambda tp: (tp.ub, tp.term))  # ascending upper bound
    ubs = np.array([tp.ub for tp in tps], np.int64)
    cum_ub = np.cumsum(ubs)
    total_ub = int(cum_ub[-1])
    strip_blocks = max(1, probe_width // index.block_size)
    cursors = [_StripCursor(tp, index.has_tf, index.impact(tp.term))
               for tp in tps]
    top_d = np.zeros(0, np.int64)
    top_s = np.zeros(0, np.int64)
    # geometric strip ramp: the first strips stay small so θ forms after
    # little decode work, then the horizon doubles so long lists take
    # O(log n_blocks) dispatches instead of O(n_blocks). Block-level
    # pruning is unaffected — pull() drops beaten blocks row-by-row
    # against the θ current at pull time, whatever the strip width.
    strip = strip_blocks

    # seed θ from the tiny lists: a term whose whole list fits in one
    # strip (a rare title term — highest impacts, handful of blocks) is
    # decoded and scored exactly up front, probing every other term only
    # at its few docids. That matures θ before ANY long block streams —
    # DAAT alone would grow θ in docid order, decoding most of the long
    # lists before the high-score docs surface. Skipped when no long list
    # exists: seeding everything would just re-derive TAAT.
    seeded = np.zeros(0, np.int64)
    seed_docs = []
    # block rows of each term gathered by probe/merge passes — the exit
    # accounting subtracts these so "pruned" means never decoded anywhere
    touched: dict[int, set] = {}
    if max(tp.n_blocks for tp in tps) > 4 * strip_blocks:
        with _trace("seed"):
            seeds = [c for c in cursors if c.tp.n_blocks <= strip_blocks]
            parts = []
            for c in seeds:
                docs, imps = c.pull(int(c.tp.last_doc[-1]), None, 0,
                                    plan=plan, stats=st)
                if docs.size:
                    parts.append((docs, imps))
                    seed_docs.append((c, docs))
            if parts:
                cand = np.unique(np.concatenate([p[0] for p in parts]))
                scores = np.zeros(cand.size, np.int64)
                for docs, imps in parts:
                    scores[np.searchsorted(cand, docs)] += imps
                for c in cursors:
                    if c not in seeds:
                        _score_term(c.tp, c.base_impact, cand,
                                    np.arange(cand.size), scores,
                                    has_tf=index.has_tf,
                                    probe_width=probe_width, plan=plan,
                                    stats=st,
                                    touched=touched.setdefault(c.tp.term,
                                                               set()))
                order = np.lexsort((cand, -scores))[:k]
                top_d, top_s = cand[order], scores[order]
                seeded = cand

    timed_out = False
    while True:
        if _expired(deadline, st, "maxscore-strip"):
            # the running top-k is exact over every strip that completed —
            # return it as the degraded partial result
            timed_out = True
            break
        full = top_d.size >= k
        theta = int(top_s[k - 1]) if full else -1
        # non-essential prefix: cumulative upper bound strictly below θ —
        # a ub-tying prefix stays essential, its docs could tie-and-win
        n_ness = (int(np.searchsorted(cum_ub, theta, side="left"))
                  if full else 0)
        if n_ness >= len(tps):
            break  # Σ all ubs < θ: nothing unseen can reach the top-k
        ess = cursors[n_ness:]
        # strip horizon: each essential term advances ≤ strip blocks
        his = [int(c.tp.last_doc[min(c.i + strip, c.tp.n_blocks) - 1])
               for c in ess if c.i < c.tp.n_blocks]
        if his:
            hi = min(his)
        else:  # all essential cursors block-exhausted: drain the buffers
            bufs = [int(c.buf_docs[-1]) for c in ess if c.buf_docs.size]
            if not bufs:
                break
            hi = max(bufs)
        parts = []
        for c in ess:
            docs, imps = c.pull(hi, theta if full else None,
                                _seeded_bound(c, total_ub, seed_docs)
                                if seed_docs else total_ub - c.tp.ub,
                                plan=plan, stats=st)
            if docs.size:
                parts.append((docs, imps))
        if parts:
            cand = np.unique(np.concatenate([p[0] for p in parts]))
            if seeded.size:
                # seeded docs are already exactly scored in the heap —
                # rescoring them here would duplicate their heap entry
                pos = np.minimum(np.searchsorted(seeded, cand),
                                 seeded.size - 1)
                cand = cand[seeded[pos] != cand]
            partial = np.zeros(cand.size, np.int64)
            for docs, imps in parts:
                pos = np.searchsorted(cand, docs)
                pos = np.minimum(pos, max(cand.size - 1, 0))
                ok = (cand[pos] == docs) if cand.size else np.zeros(
                    docs.size, bool)
                partial[pos[ok]] += imps[ok]
            scores = partial
            # probe non-essential terms in descending-bound order; drop
            # candidates as soon as even a full remaining bound can't pass
            ness = sorted((cursors[i] for i in range(n_ness)),
                          key=lambda c: -c.tp.ub)
            rem_ub = np.concatenate(
                [np.cumsum([c.tp.ub for c in reversed(ness)])[::-1],
                 [0]]) if ness else np.zeros(1, np.int64)
            alive = np.ones(cand.size, bool)
            if full:
                dead = scores + int(rem_ub[0]) < theta
                st.probes_pruned += int(dead.sum()) * len(ness)
                alive &= ~dead
            for idx, c in enumerate(ness):
                sel = np.flatnonzero(alive)
                if sel.size == 0:
                    break
                _score_term(c.tp, c.base_impact, cand, sel, scores,
                            has_tf=index.has_tf, probe_width=probe_width,
                            plan=plan, stats=st,
                            touched=touched.setdefault(c.tp.term, set()))
                if full:
                    dead = alive & (scores + int(rem_ub[idx + 1]) < theta)
                    st.probes_pruned += (int(dead.sum())
                                         * (len(ness) - idx - 1))
                    alive &= ~dead
            md = np.concatenate([top_d, cand[alive]])
            ms = np.concatenate([top_s, scores[alive]])
            order = np.lexsort((md, -ms))[:k]
            top_d, top_s = md[order], ms[order]
        strip = min(strip * STRIP_RAMP, MAX_STRIP_BLOCKS)
    # exit accounting: a block was threshold-pruned iff NO pass ever
    # decoded it — neither a strip pull (pull-pruned rows + everything
    # past the cursor frontier are the candidates) nor a non-essential
    # probe/merge gather (subtracted via ``touched``), so decoded and
    # pruned block sets stay disjoint and, per term,
    # pruned + decoded-at-least-once == n_blocks exactly. A timed-out
    # query books nothing: blocks past the frontier were abandoned by the
    # deadline, not proven beaten.
    for c in cursors if not timed_out else ():
        rows = np.concatenate(
            c.pruned_rows + [np.arange(c.i, c.tp.n_blocks)]
        ).astype(np.int64)
        c.i = c.tp.n_blocks
        got = touched.get(c.tp.term)
        if got:
            rows = rows[~np.isin(rows,
                                 np.fromiter(got, np.int64, len(got)))]
        if rows.size:
            st.count_pruned(
                int(rows.size),
                int(np.asarray(c.tp.arr.counts)[rows].sum()),
                term=c.tp.term)
    return top_d, top_s


def topk(
    index: InvertedIndex,
    terms,
    k: int,
    *,
    mode: str = "or",
    plan="auto",
    probe_width: int = DEFAULT_PROBE_WIDTH,
    stats: QueryStats | None = None,
    use_skip: bool = True,
    deadline: Deadline | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Top-k scored query: ``(docids uint32 [≤k], scores int32 [≤k])``.

    Score(d) = Σ over query terms containing d of the term's quantized
    impact at d — per-posting when the index was built with tfs
    (``InvertedIndex.has_tf``), the tf-free constant otherwise.
    ``mode="or"`` (default) is term-at-a-time over the union decode.
    ``mode="maxscore"`` returns bit-identical results via block-max
    dynamic pruning — whole blocks and candidate probes that cannot beat
    the running k-th score are never decoded (see module docstring; falls
    back to exact TAAT for resident/sharded indexes, ``use_skip=False``,
    whose arrays cannot be block-gathered on the host). ``mode="and"``
    restricts to the conjunctive candidates. ``mode="driver"`` is
    required-term top-k, the scored DAAT shape: docs containing
    ``terms[0]``, ranked by total impact over all query terms via the
    fused scoring epilogues. Results are ordered by (score desc, docid
    asc) — exact integer ties are deterministic.
    """
    if isinstance(k, bool) or not isinstance(k, (int, np.integer)) or k < 1:
        raise ValueError(f"k must be a positive integer, got {k!r}")
    k = int(k)
    with _trace("topk", mode=mode, k=k) as sp:
        out = _topk_impl(index, terms, k, mode=mode, plan=plan,
                         probe_width=probe_width, stats=stats,
                         use_skip=use_skip, deadline=deadline)
        if sp and stats is not None:
            sp.set(**stats.span_attrs())
        return out


def _topk_impl(index: InvertedIndex, terms, k: int, *, mode, plan,
               probe_width, stats, use_skip, deadline):
    if mode == "or" or (mode == "maxscore" and not use_skip):
        cand, scores = _taat_scores(index, terms, plan=plan, stats=stats,
                                    use_skip=use_skip, deadline=deadline)
    elif mode == "maxscore":
        cand, scores = _maxscore(index, terms, k, plan=plan,
                                 probe_width=probe_width, stats=stats,
                                 deadline=deadline)
    elif mode == "and":
        cand = conjunctive(index, terms, plan=plan, probe_width=probe_width,
                           stats=stats, use_skip=use_skip,
                           deadline=deadline).astype(np.int64)
        if index.has_tf:
            # per-posting impacts vary per candidate: probe each term's
            # weight stream over the conjunctive candidates
            scores = np.zeros(cand.size, np.int64)
            for t in dict.fromkeys(terms):
                tp = index.terms.get(t)
                if tp is None or tp.df == 0 or cand.size == 0:
                    continue
                if _expired(deadline, stats, "and-score-term"):
                    break
                w = min(_pow2(cand.size), probe_width)
                for s in range(0, cand.size, w):
                    chunk = cand[s:s + w].astype(np.uint32)
                    scores[s:s + len(chunk)] += _probe_pass(
                        tp, chunk, impact=index.impact(t), probe_width=w,
                        plan=plan, stats=stats, use_skip=use_skip,
                        weights=tp.impacts).astype(np.int64)
        else:
            # every conjunctive candidate is in every query term, so the
            # tf-free score is one known constant — no scoring decode
            total = sum(index.impact(t) for t in dict.fromkeys(terms))
            scores = np.full(cand.size, total, np.int64)
    elif mode == "driver":
        # required-term top-k, the real DAAT shape: candidates are the
        # docs containing terms[0], ranked by total impact over ALL query
        # terms — per chunk the fused scoring epilogue decodes only
        # skip-gathered blocks of each optional term and emits its
        # impact contribution in-kernel
        tp0 = index.terms.get(terms[0])
        if tp0 is None or tp0.df == 0:
            return np.zeros(0, np.uint32), np.zeros(0, np.int32)
        cand = _decode_blocks(tp0, 0, tp0.n_blocks, plan=plan, stats=stats,
                              use_skip=use_skip).astype(np.int64)
        if index.has_tf:
            scores = _decode_impact_stream(tp0, plan=plan, stats=stats)
        else:
            scores = np.full(cand.size, index.impact(terms[0]), np.int64)
        for t in dict.fromkeys(terms[1:]):
            tp = index.terms.get(t)
            if t == terms[0] or tp is None or tp.df == 0:
                continue
            if _expired(deadline, stats, "driver-term"):
                break
            imp = index.impact(t)
            w = min(_pow2(cand.size), probe_width)
            for s in range(0, cand.size, w):
                chunk = cand[s:s + w].astype(np.uint32)
                scores[s:s + len(chunk)] += _probe_pass(
                    tp, chunk, impact=imp, probe_width=w, plan=plan,
                    stats=stats, use_skip=use_skip,
                    weights=tp.impacts if index.has_tf else None
                ).astype(np.int64)
    else:
        raise ValueError(
            f"unknown topk mode {mode!r}; expected "
            "'or'/'maxscore'/'and'/'driver'")
    with _trace("topk-select", candidates=int(cand.size)):
        order = np.lexsort((cand, -scores))[:k]
        return cand[order].astype(np.uint32), scores[order].astype(np.int32)
