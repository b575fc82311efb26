"""Serving launcher: LM prefill+decode loop, recsys scoring, the batched
compressed serving engine (:class:`ServingEngine`), and the inverted-index
search engine (:class:`SearchEngine`).

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced --tokens 16
    PYTHONPATH=src python -m repro.launch.serve --arch two-tower-retrieval --reduced
    PYTHONPATH=src python -m repro.launch.serve --arch two-tower-retrieval \
        --reduced --devices 8 --requests 256
    PYTHONPATH=src python -m repro.launch.serve --arch search --requests 64
    PYTHONPATH=src python -m repro.launch.serve --arch search --devices 8
    PYTHONPATH=src python -m repro.launch.serve --arch search --devices 8 \
        --degraded-smoke    # kill 1 of 8 shards, assert flagged partials
    PYTHONPATH=src python -m repro.launch.serve --arch search \
        --ingest-smoke      # WAL ingest, crash a merge, recover, parity

The two-tower arch runs the ``ServingEngine``: a compressed candidate
corpus resident on the mesh (``CompressedIntArray.shard`` — block dim over
the data axis), retrieval requests microbatched to a fixed set of jitted
bucket shapes, and scoring through the fused ``dot_score`` decode epilogue
against a precomputed item-vector table. It prints aggregate QPS and
p50/p99 request latency and merges them into ``experiments/benchmarks.json``
(the cross-PR perf trajectory). See docs/serving.md.

``--devices N`` forces N host-platform devices (sets XLA_FLAGS before jax
initializes), which is how the sharded engine is exercised on CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.obs import counter_inc as _obs_counter_inc, trace as _obs_trace
# moved to repro.obs.stats (one percentile definition repo-wide);
# re-exported here because engines and benchmarks historically import it
# from this module
from repro.obs.stats import latency_summary  # noqa: F401


def serve_lm(cfg, tokens_to_gen: int, batch: int):
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro.models import lm

    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (batch, 16)), jnp.int32)
    prefill = jax.jit(lambda p, t: lm.prefill(p, t, cfg,
                                              cache_capacity=16 + tokens_to_gen))
    decode = jax.jit(lambda p, c, t: lm.decode_step(p, c, t, cfg))
    logits, cache = prefill(params, prompt)
    out = []
    t0 = time.time()
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(tokens_to_gen):
        out.append(tok)
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    jax.block_until_ready(tok)
    dt = (time.time() - t0) / tokens_to_gen
    print(f"generated {tokens_to_gen} tokens x batch {batch}: "
          f"{dt*1e3:.1f} ms/token ({batch/dt:.0f} tok/s aggregate)")
    print("sample:", np.asarray(jnp.stack(out, 1))[0, :12])


def serve_recsys(cfg, batch: int):
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro.models import recsys

    params = recsys.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    serve = jax.jit(lambda p, b: recsys.serve_scores(p, b, cfg))
    if cfg.kind == "bst":
        b = {"hist": jnp.asarray(rng.integers(1, cfg.n_items, (batch, cfg.seq_len)),
                                 jnp.int32),
             "target": jnp.asarray(rng.integers(1, cfg.n_items, batch), jnp.int32)}
    elif cfg.kind == "two_tower":
        b = {"user_id": jnp.asarray(rng.integers(1, 100, batch), jnp.int32),
             "hist": jnp.asarray(rng.integers(1, cfg.n_items,
                                              (batch, cfg.seq_len)), jnp.int32),
             "cands": jnp.asarray(rng.integers(1, cfg.n_items,
                                               cfg.serve_candidates), jnp.int32)}
    else:
        b = {"hist": jnp.asarray(rng.integers(1, cfg.n_items,
                                              (batch, cfg.seq_len)), jnp.int32),
             "cands": jnp.asarray(rng.integers(1, cfg.n_items,
                                               (batch, cfg.serve_candidates)),
                                  jnp.int32)}
    scores = jax.block_until_ready(serve(params, b))
    t0 = time.time()
    for _ in range(10):
        scores = jax.block_until_ready(serve(params, b))
    dt = (time.time() - t0) / 10
    print(f"scored batch {batch}: {dt*1e3:.2f} ms/request "
          f"(scores shape {scores.shape})")


# ---------------------------------------------------------------------------
# the batched compressed serving engine
# ---------------------------------------------------------------------------
ITEM_TABLE_CHUNK_ROWS = 1 << 18  # item-tower rows per step of the table build


def build_item_table(params, cfg, *, dtype, chunk_rows: int = ITEM_TABLE_CHUNK_ROWS):
    """The item tower over every item row, as a ``dot_score`` row table
    (``gather_kernel.row_table``: ``uint32 [padded_rows(vocab_rows), w]``).

    Built ``chunk_rows`` rows at a time into one buffer that each step
    updates in place, so the build holds the table and one chunk's
    activations, never the whole vocabulary's: at 2^23 items the first
    tower layer alone would be 17 GB. The last chunk is shifted back to end
    at the last row, so every chunk has one shape and one program; the
    padding rows then repeat the last row, as ``row_table``'s do.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.vbyte_decode.gather_kernel import pack_rows, padded_rows
    from repro.models import recsys

    V = cfg.vocab_rows
    chunk = min(chunk_rows, V)

    def rows(p, start):
        ids = start + jnp.arange(chunk, dtype=jnp.int32)
        return pack_rows(recsys.item_tower(p, ids, cfg, dtype=dtype)
                         .astype(dtype))

    @functools.partial(jax.jit, donate_argnums=0)
    def fill(table, p, start):
        return jax.lax.dynamic_update_slice(table, rows(p, start), (start, 0))

    @functools.partial(jax.jit, donate_argnums=0)
    def pad(table):
        return table.at[V:].set(table[V - 1])

    shape = jax.eval_shape(rows, params, 0)
    table = jnp.zeros((padded_rows(V), shape.shape[1]), shape.dtype)
    for start in range(0, V, chunk):
        table = fill(table, params, min(start, V - chunk))
    return pad(table)


def _mask_and_topk(ids, scores, *, top_k: int):
    import jax
    import jax.numpy as jnp

    flat_ids = ids.reshape(-1)  # [C]
    if scores.ndim == 2:  # single query: [nb, B]
        s = scores.reshape(1, -1)
    else:  # [b, nb, B] -> [b, C]
        s = scores.reshape(scores.shape[0], -1)
    s = jnp.where(flat_ids[None, :] == 0, -jnp.inf, s)  # mask pad slots
    top_s, top_i = jax.lax.top_k(s, top_k)
    return top_s, jnp.take(flat_ids, top_i)


class ServingEngine:
    """Serve retrieval / embedding-bag requests from compressed corpora.

    Architecture (docs/serving.md):

    * **Resident corpora** — one or several candidate id lists (one per
      market, say) live compressed on the device, or on the mesh:
      ``CompressedIntArray.shard(mesh, axis="data")`` places the block
      dimension across devices, and every decode runs block-parallel under
      ``shard_map`` where the bytes sit (no re-upload per request, no
      cross-device decode traffic).
    * **Serving tables in the compute dtype** — every floating parameter is
      held in ``dtype`` (bf16 by default), as inference stores its tables;
      the towers compute in that dtype either way.
    * **Precomputed item table** — the two-tower item tower runs once over
      the vocabulary at engine build, in chunks of rows
      (:func:`build_item_table`), into a ``dot_score`` row table. Serving
      fetches rows of it from HBM inside the fused ``dot_score`` decode
      kernel, so a request costs user-tower + decode-gather-dot + top-k.
    * **Bucketed microbatching** — requests are grouped to the next bucket
      size (default 1/2/4/8) and padded, so every serving step hits one of a
      fixed set of jitted shapes — no retracing in steady state. The decoded
      corpus is shared by the whole microbatch: the ``dot_score`` epilogue
      takes the bucket's ``[b, d]`` query matrix in one pass.

    ``retrieve(user_ids, hists, corpus=j)`` serves one microbatch against
    corpus ``j``; ``run_workload`` drives a request list through the
    bucketing loop and reports aggregate QPS and per-request p50/p99
    latency.
    """

    def __init__(self, params, cfg, corpus, *, mesh=None, axis="data",
                 top_k: int = 10, buckets=(1, 2, 4, 8),
                 plan="auto", dtype=None):
        import functools

        import numpy as np

        import jax
        import jax.numpy as jnp

        from repro.core import CompressedIntArray
        from repro.models import recsys
        from repro.nn import layers as nnl

        self._np, self._jax, self._jnp = np, jax, jnp
        self.cfg = cfg
        self.top_k = top_k
        self.plan = plan
        self.buckets = tuple(sorted(buckets))
        self.dtype = dtype = dtype or nnl.DEFAULT_COMPUTE_DTYPE
        self.mesh = mesh
        self.params = jax.tree.map(
            lambda x: (x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
                       else x), params)

        # resident corpora: sharded over the mesh axis, or (single device)
        # placed once — either way requests never re-upload the bytes
        corpora = ([corpus] if isinstance(corpus, CompressedIntArray)
                   else list(corpus))
        self.corpora = [c.shard(mesh, axis=axis) if mesh is not None
                        else c.replace_leaves(**c.device_operands())
                        for c in corpora]

        # the item-vector table, built once. Row 0 is the pad row;
        # dot_score pad slots gather it, and retrieve() masks id==0 before
        # top-k.
        table = build_item_table(self.params, cfg, dtype=dtype)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            table = jax.device_put(table, NamedSharding(mesh, P()))
        self.item_table = jax.block_until_ready(table)

        # per-bucket jitted user tower + top-k post; the fused decode jits
        # itself per (mesh, workload) inside the dispatch layer
        self._user_fn = jax.jit(
            lambda p, uid, hist: recsys.user_tower(p, uid, hist, cfg,
                                                   dtype=dtype))
        self._topk_fn = jax.jit(functools.partial(_mask_and_topk,
                                                  top_k=top_k))
        self._stats = []
        # liveness: one heartbeat per served microbatch; run_workload
        # reports the detector's straggler classification (empty when
        # healthy — the coordinator hook for elastic re-meshing, ft/)
        from repro.ft import StragglerDetector

        self.detector = StragglerDetector()
        self._step = 0

    @property
    def corpus(self):
        """The first resident corpus (the only one of a one-corpus engine)."""
        return self.corpora[0]

    # -- retrieval ---------------------------------------------------------
    def bucket_of(self, k: int) -> int:
        for b in self.buckets:
            if b >= k:
                return b
        return self.buckets[-1]

    def user_vectors(self, user_ids, hists):
        """The ``[b, d]`` query matrix ``retrieve`` scores with: the user
        tower over [b] user ids and [b, L] histories, in the engine's
        dtype."""
        return self._user_fn(self.params, user_ids, hists)

    def retrieve(self, user_ids, hists, *, corpus: int = 0):
        """Serve one microbatch against resident corpus ``corpus``: [b]
        user ids + [b, L] histories → (scores [b, k], item ids [b, k]).
        b must be one of the buckets.

        Traced as one ``retrieve`` span with children
        ``retrieve.user_tower``, ``retrieve.score`` (the fused
        ``dispatch.decode`` call) and ``retrieve.topk``; each returns before
        the device has finished. Counts ``retrieval_candidates_total`` (the
        corpus's ids, each scored for the whole microbatch) and
        ``dot_score_rows_fetched_total`` (table rows the scoring fetches,
        padding slots included)."""
        from repro.kernels.vbyte_decode import dispatch

        arr = self.corpora[corpus]
        with _obs_trace("retrieve", corpus=int(corpus),
                        bucket=int(user_ids.shape[0])):
            with _obs_trace("retrieve.user_tower"):
                u = self.user_vectors(user_ids, hists)  # [b, d]
            with _obs_trace("retrieve.score"):
                ids, scores = dispatch.decode(
                    arr, epilogue="dot_score",
                    epilogue_operands={"table": self.item_table, "query": u},
                    plan=self.plan)
            with _obs_trace("retrieve.topk"):
                out = self._topk_fn(ids, scores)
        _obs_counter_inc("retrieval_candidates_total", arr.n)
        _obs_counter_inc("dot_score_rows_fetched_total", ids.size)
        return out

    # -- embedding-bag endpoint -------------------------------------------
    def embed_bags(self, bags, *, format="vbyte"):
        """Pooled embeddings for ragged id bags (one request = one bag).

        The bag list is compressed on the host (one block per bag, ragged
        layout) and reduced in the decode kernel's ``bag_sum`` epilogue —
        the microbatched analogue of ``user_tower_compressed``'s history
        path. Returns ``[len(bags), d]``.
        """
        from repro.core import CompressedIntArray
        from repro.nn.embedding_bag import embedding_bag_compressed

        k = len(bags)
        b = self.bucket_of(k)
        padded = list(bags) + [[] for _ in range(b - k)]
        arr = CompressedIntArray.encode_ragged(
            padded, format=format, block_size=self.cfg.seq_len,
            differential=False)
        out = embedding_bag_compressed(
            self.params["item_id_emb"]["emb"], arr, mode="mean",
            plan=self.plan, dtype=self.dtype)
        return out[:k]

    # -- workload driver ---------------------------------------------------
    def warmup(self):
        """Compile every bucket shape against every corpus shape up front
        (excluded from latencies)."""
        np, jnp = self._np, self._jnp
        rng = np.random.default_rng(0)
        shapes = {}
        for j, c in enumerate(self.corpora):
            shapes.setdefault(tuple(v.shape for v in c.device_operands()
                                    .values()), j)
        for b in self.buckets:
            uid = jnp.asarray(rng.integers(1, max(self.cfg.n_users, 2), b),
                              jnp.int32)
            hist = jnp.asarray(
                rng.integers(1, self.cfg.n_items, (b, self.cfg.seq_len)),
                jnp.int32)
            for j in shapes.values():
                self._jax.block_until_ready(self.retrieve(uid, hist,
                                                          corpus=j))

    def run_workload(self, requests, *, max_batch: int | None = None) -> dict:
        """Drive (user_id, hist) requests through the microbatching loop.

        Requests are drained greedily up to the largest bucket, padded to
        the bucket shape, and served. This is a closed-loop drain of a
        pre-built request list, so the reported p50/p99 are per-request
        **service** latencies (host marshal + engine step for the request's
        microbatch); queueing delay behind earlier batches is not included —
        aggregate QPS over the whole drain captures that side.
        """
        np, jnp, jax = self._np, self._jnp, self._jax
        # a microbatch can never exceed the largest jitted bucket shape
        max_batch = min(max_batch or self.buckets[-1], self.buckets[-1])
        lat = []
        i = 0
        t_start = time.perf_counter()
        while i < len(requests):
            take = min(max_batch, len(requests) - i)
            b = self.bucket_of(take)
            chunk = requests[i:i + take]
            t0 = time.perf_counter()
            with _obs_trace("microbatch", bucket=int(b), requests=int(take)):
                uid = np.full(b, 1, np.int32)
                hist = np.ones((b, self.cfg.seq_len), np.int32)
                for j, (u, h) in enumerate(chunk):
                    uid[j] = u
                    hist[j] = h
                top_s, top_i = self.retrieve(jnp.asarray(uid),
                                             jnp.asarray(hist))
                jax.block_until_ready((top_s, top_i))
            _obs_counter_inc("serve_requests_total", take, engine="serving")
            dt = time.perf_counter() - t0
            lat.extend([dt] * take)  # whole microbatch completes together
            self.detector.heartbeat("serve-host", self._step)
            self._step += 1
            i += take
        wall = time.perf_counter() - t_start
        stats = {
            "n_requests": len(requests),
            "n_devices": (int(self.mesh.devices.size)
                          if self.mesh is not None else 1),
            **latency_summary(lat, wall, len(requests)),
            "top_k": self.top_k,
            "corpus_n": self.corpus.n,
            "buckets": list(self.buckets),
            "stragglers": self.detector.stragglers(),
        }
        self._stats.append(stats)
        return stats


# ---------------------------------------------------------------------------
# the inverted-index search engine
# ---------------------------------------------------------------------------
class SearchEngine:
    """Serve boolean / top-k queries from a resident compressed inverted index.

    Architecture (docs/index.md):

    * **Resident index** — per-term compressed posting lists stay loaded
      for the engine's lifetime. Single-device, the term leaves stay host-
      side so the skip tables can slice out just the overlapping block
      ranges before upload (block-level pruning). With a ``mesh``, every
      term's block dimension is sharded across the devices instead
      (``CompressedIntArray.shard``) and each query decodes block-parallel
      under ``shard_map`` where the bytes live (``use_skip=False`` — the
      mesh replaces host slicing as the parallelism mechanism); the
      per-shard ``bm25_accum`` partials come back as one sharded
      ``[n_blocks, P]`` output whose host-side block-sum is the partial
      top-k merge.
    * **Microbatched queries** — candidate sets are processed in fixed
      ``probe_width`` chunks, so every membership/scoring step hits a
      bounded set of jitted shapes — no steady-state retracing, the
      query-engine analogue of ``ServingEngine``'s request buckets.

    ``search(terms, mode=...)`` serves one query; ``run_workload`` drives a
    query list and reports QPS, p50/p99 latency, and decode-vs-skip block
    accounting.

    **Degraded-mode serving** (docs/robustness.md): with ``validate=True``
    every term's streams are validated at startup — terms whose payload /
    metadata / checksum column fails are **quarantined** (dropped from
    queries, which come back flagged ``degraded``), terms whose
    ``max_impact`` bound is unsafe are kept but force a
    ``topk_maxscore`` → exhaustive-TAAT fallback (exact, just slower).
    Per-request ``Deadline`` budgets (``deadline_s``), bounded
    retry-with-backoff on transient :class:`DecodeError`\\ s (a failure
    carrying term coordinates quarantines that segment and the query is
    re-answered from the rest), and a logical-shard health layer
    (``n_shards`` + :class:`~repro.ft.StragglerDetector`: ``heartbeat`` /
    ``check_health`` / ``kill_shard`` / ``heal``) keep the engine answering
    — partial and flagged, never hung, never silently wrong.
    """

    def __init__(self, index, *, mesh=None, axis="data", top_k: int = 10,
                 plan="auto", probe_width: int = 512,
                 validate: bool = False, deep_validate: bool = False,
                 deadline_s: float | None = None, max_retries: int = 2,
                 backoff_s: float = 0.0, fault_hook=None,
                 n_shards: int = 0, clock=None):
        from dataclasses import replace as _dc_replace

        from repro.ft import StragglerDetector, shard_intervals

        self.index = index
        self.mesh = mesh
        self.top_k = top_k
        self.plan = plan
        self.probe_width = probe_width
        self.use_skip = mesh is None
        # -- robustness state ------------------------------------------------
        self.deadline_s = deadline_s
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.fault_hook = fault_hook  # fault_hook(attempt, terms, mode):
        #   raise DecodeError to inject a failure for attempt k (tests/CI)
        self.clock = clock or time.monotonic
        self.quarantined: dict = {}  # term -> reason (startup or at-serve)
        self.bound_unsafe: set = set()  # terms with unsafe max_impact bounds
        self.serve_stats = {"errors": 0, "retries": 0,
                            "quarantined_terms": 0, "quarantined_blocks": 0,
                            "bound_fallbacks": 0, "degraded_responses": 0}
        # logical shards: the sorted term list partitioned into n_shards
        # contiguous intervals (ft.elastic.shard_intervals) — the unit of
        # simulated host loss. A dead shard's terms are dropped from
        # queries (flagged degraded) until heal() re-partitions ownership.
        self.term_order = sorted(index.terms)
        self.n_shards = int(n_shards)
        self.detector = StragglerDetector()
        self.dead_shards: set = set()
        self.shard_of: dict = {}
        if self.n_shards:
            self._assign_shards(shard_intervals(len(self.term_order),
                                                self.n_shards))
        if validate:
            self._validate_index(deep=deep_validate)
        if mesh is not None:
            # shard every term's blocks across the mesh, once, up front —
            # the per-posting impact stream too (same block layout, so the
            # weighted scoring epilogues see aligned shards)
            sharded = {}
            for t, tp in index.terms.items():
                if tp.df:
                    arr = tp.arr.shard(mesh, axis=axis)
                    imp = (tp.impacts.shard(mesh, axis=axis)
                           if tp.impacts is not None else None)
                else:
                    arr, imp = tp.arr, tp.impacts
                sharded[t] = _dc_replace(tp, arr=arr, impacts=imp)
            self.index = _dc_replace(index, terms=sharded)
        self._stats = []

    # -- startup validation / quarantine ----------------------------------
    def _validate_index(self, *, deep: bool):
        """Gate every term at startup (docs/robustness.md).

        Structure + stream validation, skip-table/df invariants, and — when
        the stream carries a checksum column — a checksum-verified decode
        through the fused epilogue. Failing terms are quarantined. A
        :class:`BoundViolationError` (unsafe ``max_impact``, only checked
        with ``deep=True``) instead marks the term ``bound_unsafe``: its
        results are still exact under every mode except MaxScore pruning,
        so the engine keeps it and falls back to exhaustive TAAT.
        """
        from repro.robustness import (BoundViolationError, DecodeError,
                                      decode_checked, validate_array,
                                      validate_meta)

        for t in self.term_order:
            tp = self.index.terms[t]
            if not tp.df:
                continue
            try:
                validate_array(tp.arr, term=t)
                if tp.impacts is not None:
                    validate_array(tp.impacts, term=t)
                if tp.arr.checksums is not None:
                    decode_checked(tp.arr, plan=self.plan, term=t)
                if tp.impacts is not None and tp.impacts.checksums is not None:
                    decode_checked(tp.impacts, plan=self.plan, term=t)
                validate_meta(tp, deep=deep)
            except BoundViolationError:
                self.bound_unsafe.add(t)
            except DecodeError as e:
                self._quarantine(t, str(e))

    def _bump(self, key: str, n: int = 1, **labels):
        """Increment one robustness counter: the ``serve_stats`` dict (the
        stable in-process API benchmarks/tests read and reset) and, when
        telemetry is installed, the ``serve_<key>_total`` labeled counter
        in the metrics registry (docs/observability.md)."""
        self.serve_stats[key] += n
        _obs_counter_inc(f"serve_{key}_total", n, engine="search", **labels)

    def _quarantine(self, term, reason: str):
        if term in self.quarantined:
            return
        self.quarantined[term] = reason
        self._bump("quarantined_terms")
        tp = self.index.terms.get(term)
        if tp is not None:
            self._bump("quarantined_blocks", tp.n_blocks)

    # -- logical-shard health (ft.heartbeat + ft.elastic) ------------------
    def _assign_shards(self, intervals):
        self.shards = list(intervals)
        self.shard_of = {t: s for s, (lo, hi) in enumerate(self.shards)
                         for t in self.term_order[lo:hi]}

    def heartbeat(self, shard: int, step: int, now: float | None = None):
        """One liveness beat from a logical shard (tests drive sim time)."""
        self.detector.heartbeat(f"shard{shard}", step,
                                self.clock() if now is None else now)

    def check_health(self, now: float | None = None) -> dict:
        """Classify shards via the straggler detector; newly-'dead' shards
        are killed (their terms drop from queries until :meth:`heal`)."""
        report = self.detector.stragglers(
            self.clock() if now is None else now)
        for host, state in report.items():
            if state == "dead" and host.startswith("shard"):
                self.dead_shards.add(int(host[len("shard"):]))
        return report

    def kill_shard(self, shard: int):
        """Simulate losing one logical shard (CI degraded-serving smoke)."""
        self.dead_shards.add(int(shard))

    def heal(self):
        """Re-partition term ownership over the surviving shards.

        Uses :func:`repro.ft.elastic.reshard_plan` to map each new interval
        onto slices of the old partition (returned for inspection), then
        reassigns every term to a live owner — after healing no query is
        degraded by shard loss (the terms were host-resident all along;
        what died was the logical serving owner).
        """
        from repro.ft import reshard_plan, shard_intervals

        if not self.dead_shards:
            return []
        n_alive = self.n_shards - len(self.dead_shards)
        if n_alive <= 0:
            raise RuntimeError("no live shards left to heal onto")
        plan = reshard_plan(len(self.term_order), self.n_shards, n_alive)
        for s in self.dead_shards:
            self.detector.hosts.pop(f"shard{s}", None)
        self.n_shards = n_alive
        self._assign_shards(shard_intervals(len(self.term_order), n_alive))
        self.dead_shards = set()
        return plan

    # -- queries -----------------------------------------------------------
    def _run_query(self, terms, mode: str, stats, deadline):
        from repro.index import conjunctive, disjunctive, topk

        if not terms:  # everything quarantined / dead: empty, well-typed
            import numpy as np

            empty = np.zeros(0, np.uint32)
            return (empty if mode in ("and", "or")
                    else (empty, np.zeros(0, np.int32)))
        kw = dict(plan=self.plan, stats=stats, use_skip=self.use_skip,
                  deadline=deadline)
        if mode == "and":
            return conjunctive(self.index, terms,
                               probe_width=self.probe_width, **kw)
        if mode == "or":
            return disjunctive(self.index, terms, **kw)
        if mode in ("topk", "topk_driver", "topk_maxscore"):
            sub = {"topk": "or", "topk_driver": "driver",
                   "topk_maxscore": "maxscore"}[mode]
            return topk(self.index, terms, self.top_k, mode=sub,
                        probe_width=self.probe_width, **kw)
        raise ValueError(f"unknown query mode {mode!r}")

    def search(self, terms, mode: str = "and", *, stats=None, deadline=None):
        """One query. ``mode``: 'and' | 'or' → sorted uint32 docids;
        'topk' (disjunctive TAAT) | 'topk_maxscore' (block-max pruned,
        bit-identical results) | 'topk_driver' (required-term DAAT) →
        (docids, int32 scores), ordered (score desc, docid asc).

        Hardened path: quarantined / dead-shard terms are dropped (query
        flagged ``degraded`` via ``stats``), unsafe-bound terms force
        ``topk_maxscore`` → exhaustive TAAT, a :class:`DecodeError` raised
        mid-answer is retried up to ``max_retries`` times (term-coordinate
        failures quarantine the segment first), and an expired ``deadline``
        (or ``deadline_s`` default) yields a smaller, flagged result. The
        query never hangs and never returns silently-wrong data.
        """
        from repro.index import QueryStats
        from repro.robustness import Deadline, DecodeError

        with _obs_trace("request", mode=mode, terms=len(terms)) as rspan:
            with _obs_trace("admission"):
                qst = QueryStats()  # per-call: degraded flag is per query
                if deadline is None and self.deadline_s is not None:
                    deadline = Deadline(self.deadline_s, clock=self.clock)
                live = []
                for t in dict.fromkeys(terms):
                    if t in self.quarantined:
                        qst.mark_degraded(f"quarantined-term:{t}")
                        tp = self.index.terms.get(t)
                        qst.quarantined_blocks += tp.n_blocks if tp else 0
                    elif self.shard_of.get(t) in self.dead_shards:
                        qst.mark_degraded(f"dead-shard:{self.shard_of[t]}")
                    else:
                        live.append(t)
                eff = mode
                if mode == "topk_maxscore" and any(t in self.bound_unsafe
                                                   for t in live):
                    eff = "topk"  # exhaustive TAAT: exact without bounds
                    qst.bound_fallbacks += 1
                    self._bump("bound_fallbacks")
            with _obs_trace("execute", mode=eff):
                attempt = 0
                while True:
                    try:
                        if self.fault_hook is not None:
                            self.fault_hook(attempt, live, eff)
                        out = self._run_query(live, eff, qst, deadline)
                        break
                    except DecodeError as e:
                        qst.errors += 1
                        self._bump("errors", error=type(e).__name__)
                        term = getattr(e, "term", None)
                        if term is not None and term in live:
                            # the segment itself is bad — quarantine it and
                            # answer the query from the remaining terms
                            self._quarantine(term, str(e))
                            live = [t for t in live if t != term]
                            qst.mark_degraded(f"quarantined-term:{term}")
                        elif attempt >= self.max_retries:
                            qst.mark_degraded("retries-exhausted")
                            out = self._run_query([], eff, qst, deadline)
                            break
                        else:
                            attempt += 1
                            qst.retries += 1
                            self._bump("retries")
                            if self.backoff_s:
                                time.sleep(self.backoff_s * attempt)
            with _obs_trace("finalize"):
                _obs_counter_inc("serve_requests_total", mode=mode,
                                 engine="search")
                if qst.degraded:
                    self._bump("degraded_responses")
                    for r in qst.degraded_reasons:
                        cat, _, where = r.partition(":")
                        _obs_counter_inc("serve_degraded_total", reason=cat,
                                         engine="search")
                        if cat == "deadline":
                            _obs_counter_inc("serve_deadline_hits_total",
                                             where=where, engine="search")
                if rspan:
                    rspan.set(mode_effective=eff, degraded=qst.degraded,
                              n_results=int(len(out[0]) if isinstance(
                                  out, tuple) else len(out)))
                if stats is not None:
                    stats.merge(qst)
            return out

    def warmup(self, queries):
        """Run each (mode, terms) query once to compile its shapes."""
        for mode, terms in queries:
            self.search(terms, mode)

    def run_workload(self, queries) -> dict:
        """Drive (mode, terms) queries sequentially; aggregate QPS/latency
        plus the skip-table decode accounting over the whole workload.
        Each query posts a heartbeat for every live logical shard, so a
        killed shard goes stale and ``check_health`` classifies it dead."""
        from repro.index import QueryStats

        st = QueryStats()
        serve_before = dict(self.serve_stats)
        lat = []
        n_results = 0
        step = 0
        t_start = time.perf_counter()
        for mode, terms in queries:
            t0 = time.perf_counter()
            out = self.search(terms, mode, stats=st)
            lat.append(time.perf_counter() - t0)
            n_results += len(out[0] if isinstance(out, tuple) else out)
            for s in range(self.n_shards):
                if s not in self.dead_shards:
                    self.heartbeat(s, step)
            step += 1
        wall = time.perf_counter() - t_start
        # blocks considered = decoded + skip-table-skipped (both per
        # decode/probe pass) + threshold-pruned (never decoded by ANY
        # pass — disjoint from decoded, the partition the accounting
        # tests prove per term)
        total_blocks = (st.blocks_decoded + st.blocks_skipped
                        + st.blocks_pruned)
        total_postings = st.ints_decoded + st.postings_pruned
        stats = {
            "n_queries": len(queries),
            "n_devices": (int(self.mesh.devices.size)
                          if self.mesh is not None else 1),
            **latency_summary(lat, wall, len(queries)),
            "n_results": int(n_results),
            "blocks_decoded": st.blocks_decoded,
            "block_skip_rate": round(st.blocks_skipped / total_blocks, 3)
                               if total_blocks else 0.0,
            "pruned_block_rate": round(st.blocks_pruned / total_blocks, 3)
                                 if total_blocks else 0.0,
            "pruned_impact_rate": round(st.postings_pruned / total_postings,
                                        3) if total_postings else 0.0,
            "probes_pruned": st.probes_pruned,
            "rows_gathered": st.rows_gathered,
            "ints_decoded": st.ints_decoded,
            "impact_ints_decoded": st.impact_ints_decoded,
            "decoded_ints_per_s": round(st.ints_decoded / wall, 1),
            "index": self.index.stats(),
            # robustness accounting over this workload (docs/robustness.md)
            "errors": st.errors,
            "retries": st.retries,
            "degraded_responses": (self.serve_stats["degraded_responses"]
                                   - serve_before["degraded_responses"]),
            "quarantined_terms": self.serve_stats["quarantined_terms"],
            "quarantined_blocks": self.serve_stats["quarantined_blocks"],
            "bound_fallbacks": st.bound_fallbacks,
            "dead_shards": sorted(self.dead_shards),
        }
        self._stats.append(stats)
        return stats


def search_queries(rng, index, n_queries: int, *,
                   terms_per_query=(1, 2, 3, 5),
                   modes=("and", "or", "topk", "topk_driver",
                          "topk_maxscore")) -> list:
    """Synthetic query mix over an index's terms: (mode, terms) pairs."""
    term_ids = sorted(index.terms)
    out = []
    for i in range(n_queries):
        k = int(rng.choice(terms_per_query))
        terms = [int(t) for t in
                 rng.choice(term_ids, size=min(k, len(term_ids)),
                            replace=False)]
        out.append((modes[i % len(modes)], terms))
    return out


def stage_latency_summary(tracer, stages=("decode", "gallop", "merge",
                                          "score", "topk", "topk-select",
                                          "seed", "request", "admission",
                                          "execute")) -> dict:
    """Per-stage latency block from a tracer's finished spans: for each
    stage name with ≥1 span, count + p50/p99/mean milliseconds. This is the
    ``observability`` benchmarks.json section and the report headline."""
    from repro.obs.stats import percentile

    out = {}
    for name in stages:
        ds = [d * 1e3 for d in tracer.durations(name)]
        if ds:
            out[name] = {"count": len(ds),
                         "p50_ms": round(percentile(ds, 50), 3),
                         "p99_ms": round(percentile(ds, 99), 3),
                         "mean_ms": round(sum(ds) / len(ds), 3)}
    return out


def write_metrics_out(tele, out_dir: str) -> dict:
    """Export one telemetry capture: Prometheus exposition
    (``metrics.prom``), the JSONL span log (``trace.jsonl``), and the
    Chrome/Perfetto trace (``trace-chrome.json``). Returns the paths."""
    os.makedirs(out_dir, exist_ok=True)
    paths = {"prometheus": os.path.join(out_dir, "metrics.prom"),
             "jsonl": os.path.join(out_dir, "trace.jsonl"),
             "chrome": os.path.join(out_dir, "trace-chrome.json")}
    with open(paths["prometheus"], "w") as f:
        f.write(tele.registry.to_prometheus())
    tele.tracer.write_jsonl(paths["jsonl"])
    tele.tracer.write_chrome_trace(paths["chrome"])
    return paths


def serving_mesh(n_devices: int):
    """A ``data`` mesh over the first ``n_devices`` attached devices, or
    None for one device. The count is the caller's choice: a host with more
    chips attached does not silently turn a one-device run into a sharded
    one."""
    import numpy as np

    import jax

    devices = jax.devices()
    if n_devices > len(devices):
        raise ValueError(f"{n_devices} devices requested, {len(devices)} "
                         "attached")
    if n_devices <= 1:
        return None
    return jax.sharding.Mesh(np.array(devices[:n_devices]), ("data",))


def serve_search(*, queries: int, group_k: int = 10, n_lists: int = 16,
                 top_k: int = 10, record: bool = True, seed: int = 0,
                 metrics_out: str | None = None, n_devices: int = 1) -> dict:
    """Build a synthetic posting-list index and drive a query workload.

    ``metrics_out=DIR`` installs a telemetry capture around the measured
    workload and writes the three exports there (see
    :func:`write_metrics_out`); the per-stage latency breakdown is merged
    into benchmarks.json as the ``observability`` section.
    """
    import numpy as np

    from repro.data.synthetic import posting_list_group, posting_tfs
    from repro.index import build_index

    rng = np.random.default_rng(seed)
    universe = 1 << 22
    lists = dict(enumerate(
        posting_list_group(rng, group_k, n_lists, universe=universe)))
    tfs = {t: posting_tfs(rng, len(v)) for t, v in lists.items()}
    index = build_index(lists, tfs=tfs, n_docs=universe)
    mesh = serving_mesh(n_devices)
    print(f"index: {index.n_terms} terms, {index.n_postings} postings, "
          f"{index.bits_per_int:.2f} bits/int over {n_devices} device(s)")

    engine = SearchEngine(index, mesh=mesh, top_k=top_k)
    qs = search_queries(rng, index, queries)
    engine.warmup(qs)  # compile every query's shapes; timing is steady-state
    tele = None
    if metrics_out:
        from repro import obs

        tele = obs.Telemetry()
        obs.install(tele)
    try:
        stats = engine.run_workload(qs)
    finally:
        if tele is not None:
            from repro import obs

            obs.uninstall()
    print(f"served {stats['n_queries']} queries on {stats['n_devices']} "
          f"device(s): {stats['qps']} QPS, p50 {stats['p50_ms']} ms, "
          f"p99 {stats['p99_ms']} ms, block skip rate "
          f"{stats['block_skip_rate']}, pruned block rate "
          f"{stats['pruned_block_rate']}")
    if tele is not None:
        paths = write_metrics_out(tele, metrics_out)
        obs_stats = {
            "n_queries": len(qs),
            "n_traces": len(tele.tracer.trees()),
            "stages": stage_latency_summary(tele.tracer),
        }
        print(f"telemetry capture -> {metrics_out} "
              f"({obs_stats['n_traces']} span trees)")
        if record:
            record_benchmark("observability", obs_stats)
        stats = dict(stats, observability=obs_stats, metrics_paths=paths)
    if record:
        path = record_benchmark("search_engine",
                                {k: v for k, v in stats.items()
                                 if k not in ("observability",
                                              "metrics_paths")})
        print(f"recorded -> {path}")
    return stats


def serve_search_degraded(*, queries: int = 32, group_k: int = 8,
                          n_lists: int = 16, n_shards: int = 8,
                          top_k: int = 10, record: bool = True,
                          seed: int = 0, n_devices: int = 1) -> dict:
    """CI degraded-serving smoke (docs/robustness.md).

    Builds a checksummed index served over ``n_shards`` logical shards,
    runs a healthy workload, then silences one shard's heartbeats until the
    straggler detector classifies it dead — queries touching its terms must
    come back as *flagged partial results* (smaller, ``degraded``, never an
    exception or a hang). ``heal()`` re-partitions ownership over the
    survivors and the same workload must return bit-identical to the
    healthy baseline. Raises ``AssertionError`` on any violation.
    """
    import numpy as np

    from repro.data.synthetic import posting_list_group, posting_tfs
    from repro.index import QueryStats, build_index

    rng = np.random.default_rng(seed)
    universe = 1 << 20
    lists = dict(enumerate(
        posting_list_group(rng, group_k, n_lists, universe=universe)))
    tfs = {t: posting_tfs(rng, len(v)) for t, v in lists.items()}
    index = build_index(lists, tfs=tfs, n_docs=universe, checksum=True)
    mesh = serving_mesh(n_devices)

    sim = {"t": 0.0}  # injectable clock: the smoke is deterministic

    def clock():
        sim["t"] += 1e-3  # every observation ticks, like a real clock
        return sim["t"]

    engine = SearchEngine(index, mesh=mesh, top_k=top_k, validate=True,
                          n_shards=n_shards, clock=clock)
    print(f"degraded smoke: {index.n_terms} terms over {n_shards} logical "
          f"shards, {n_devices} device(s), validate=True "
          f"(quarantined={engine.serve_stats['quarantined_terms']})")
    assert not engine.quarantined and not engine.bound_unsafe

    victim = 3
    lo, hi = engine.shards[victim]
    victim_terms = engine.term_order[lo:hi]
    qs = search_queries(rng, index, queries)
    qs.append(("or", [victim_terms[0]]))  # at least one query is hit
    engine.warmup(qs)

    clean = [engine.search(terms, mode) for mode, terms in qs]
    healthy = engine.run_workload(qs)  # every query beats all 8 shards
    assert healthy["degraded_responses"] == 0, healthy

    # the victim goes silent while the survivors keep beating: its
    # staleness blows past dead_factor × median step time and
    # check_health (not a manual kill) takes it out of rotation
    for i in range(5):
        sim["t"] += 1.0
        for s in range(n_shards):
            if s != victim:
                engine.heartbeat(s, 1000 + i)
    report = engine.check_health()
    assert report.get(f"shard{victim}") == "dead", report
    assert engine.dead_shards == {victim}

    degraded = 0
    for (mode, terms), ref in zip(qs, clean):
        st = QueryStats()
        out = engine.search(terms, mode, stats=st)
        touched = any(t in victim_terms for t in terms)
        assert st.degraded == touched, (mode, terms)
        if touched:
            degraded += 1
            # partial: the surviving terms' exact answer, a well-formed
            # subset of the healthy result for or/topk modes
            ids = out[0] if isinstance(out, tuple) else out
            ref_ids = ref[0] if isinstance(ref, tuple) else ref
            if mode == "or":
                assert np.isin(ids, ref_ids).all()
        else:
            a = out if isinstance(out, tuple) else (out,)
            b = ref if isinstance(ref, tuple) else (ref,)
            assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert degraded > 0
    print(f"killed shard {victim}: {degraded}/{len(qs)} responses flagged "
          "degraded, the rest bit-identical to healthy")

    plan = engine.heal()
    assert engine.dead_shards == set() and len(plan) == engine.n_shards
    for (mode, terms), ref in zip(qs, clean):
        st = QueryStats()
        out = engine.search(terms, mode, stats=st)
        assert not st.degraded
        a = out if isinstance(out, tuple) else (out,)
        b = ref if isinstance(ref, tuple) else (ref,)
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
    stats = {
        "n_queries": len(qs),
        "n_shards": n_shards,
        "n_devices": n_devices,
        "degraded_responses": degraded,
        "healed_shards": engine.n_shards,
        **{k: v for k, v in engine.serve_stats.items()},
    }
    print(f"healed onto {engine.n_shards} shards: all {len(qs)} responses "
          "bit-identical to healthy — degraded-serving smoke OK")
    if record:
        path = record_benchmark("search_degraded_smoke", stats)
        print(f"recorded -> {path}")
    return stats


class LiveSearchEngine:
    """Serving facade over a mutable :class:`repro.index.ingest.LiveIndex`.

    The static ``SearchEngine`` above serves one immutable index; this one
    serves the live logical state (main segment − tombstones ∪ delta) and
    surfaces the ingestion layer's degraded states the same way the rest
    of the serving stack does (docs/ingestion.md):

    * ``replaying`` — the index is still replaying its WAL after a
      restart; answers are correct for the replayed prefix and flagged
      degraded via ``QueryStats``.
    * ``merge_in_progress`` — a background merge is draining the delta;
      queries keep full fidelity (bit-identical to quiescent — the fuzz
      suite proves it), the flag is reported in workload stats for
      capacity planning.

    Mutations (``add``/``delete``) proxy to the live index and are durable
    (WAL-appended + fsynced) before they return.
    """

    def __init__(self, live, *, top_k: int = 10):
        self.live = live
        self.top_k = top_k
        self._stats: list[dict] = []

    def add(self, doc, terms):
        self.live.add(doc, terms)

    def delete(self, doc):
        self.live.delete(doc)

    def search(self, terms, mode: str = "and", *, stats=None):
        with _obs_trace("request", mode=mode, terms=len(terms),
                        engine="live") as rspan:
            _obs_counter_inc("serve_requests_total", mode=mode,
                             engine="live")
            if mode == "topk":
                out = self.live.search(terms, mode="topk", k=self.top_k,
                                       stats=stats)
            else:
                out = self.live.search(terms, mode=mode, stats=stats)
            if rspan and stats is not None:
                rspan.set(degraded=stats.degraded,
                          state=self.live.state)
            return out

    def run_workload(self, queries) -> dict:
        """Drive (mode, terms) queries; aggregate QPS/latency plus the
        live-index accounting (delta-sourced hits, tombstone suppressions,
        merge/replay states)."""
        from repro.index import QueryStats

        st = QueryStats()
        lat = []
        n_results = 0
        degraded = 0
        merging = 0
        t_start = time.perf_counter()
        for mode, terms in queries:
            q = QueryStats()
            t0 = time.perf_counter()
            out = self.search(terms, mode, stats=q)
            lat.append(time.perf_counter() - t0)
            n_results += len(out[0] if isinstance(out, tuple) else out)
            degraded += int(q.degraded)
            merging += int(self.live.state == "merge_in_progress")
            st.merge(q)
        wall = time.perf_counter() - t_start
        stats = {
            "n_queries": len(queries),
            **latency_summary(lat, wall, len(queries)),
            "n_results": int(n_results),
            "epoch": self.live.epoch,
            "state": self.live.state,
            "merge_in_progress_queries": merging,
            "n_delta_docs": self.live.n_delta_docs,
            "pending_ops": self.live.n_pending,
            "doc_count": self.live.doc_count(),
            "blocks_decoded": st.blocks_decoded,
            "ints_decoded": st.ints_decoded,
            "delta_postings": st.delta_postings,
            "delta_hits": st.delta_hits,
            "tombstones_applied": st.tombstones_applied,
            "degraded_responses": degraded,
        }
        self._stats.append(stats)
        return stats


def _ingest_ops(rng, *, n_ops: int, universe: int, n_terms: int):
    """A seeded add/delete op stream plus the resulting logical state."""
    state: dict[int, dict[int, int]] = {}
    ops = []
    for _ in range(n_ops):
        if state and rng.random() < 0.25:
            doc = int(rng.choice(sorted(state)))
            ops.append(("del", doc, None))
            del state[doc]
        else:
            doc = int(rng.integers(universe))
            if doc in state:
                continue
            k = int(rng.integers(1, 5))
            terms = {int(t): int(rng.integers(1, 5))
                     for t in rng.choice(n_terms, size=k, replace=False)}
            ops.append(("add", doc, terms))
            state[doc] = terms
    return ops, state


def _rebuild_oracle(state: dict, *, universe: int, block_size: int = 128):
    """Rebuilt-from-scratch index over a logical doc→terms state — the
    definition of correct the live index is compared against."""
    import numpy as np

    from repro.index import build_index

    lists: dict[int, list] = {}
    tfs: dict[int, list] = {}
    for doc in sorted(state):
        for t, tf in state[doc].items():
            lists.setdefault(t, []).append(doc)
            tfs.setdefault(t, []).append(tf)
    return build_index(
        {t: np.asarray(v, np.int64) for t, v in lists.items()},
        tfs={t: np.asarray(v, np.int64) for t, v in tfs.items()},
        format="auto", n_docs=universe, block_size=block_size,
        checksum=True)


def serve_ingest_smoke(*, ops: int = 200, queries: int = 24,
                       top_k: int = 10, record: bool = True,
                       seed: int = 0) -> dict:
    """CI end-to-end ingestion smoke (docs/ingestion.md).

    Ingest a seeded add/delete stream into a WAL-backed ``LiveIndex``,
    **crash** the background merge at a seeded-random named crash point,
    recover by reopening the directory, and assert query parity —
    AND/OR/top-k bit-identical to an index rebuilt from scratch from the
    acknowledged logical state — before and after the crash, during the
    (retried) merge at every crash point, and after it commits. Raises
    ``AssertionError`` on any divergence.
    """
    import shutil
    import tempfile

    import numpy as np

    from repro.index import CRASH_POINTS, CrashPoint, LiveIndex, QueryStats
    from repro.index import query as iq

    rng = np.random.default_rng(seed)
    universe = 50_000
    n_terms = 12
    workdir = tempfile.mkdtemp(prefix="ingest_smoke_")
    try:
        live = LiveIndex(workdir, n_docs=universe, fsync=False)
        stream, state = _ingest_ops(rng, n_ops=ops, universe=universe,
                                    n_terms=n_terms)
        for kind, doc, terms in stream:
            (live.add(doc, terms) if kind == "add" else live.delete(doc))

        qs = []
        for _ in range(queries):
            k = int(rng.integers(1, 4))
            terms = [int(t) for t in rng.choice(n_terms, size=k,
                                                replace=False)]
            qs.append((("and", "or", "topk")[int(rng.integers(3))], terms))

        def assert_parity(ix, tag):
            oracle = _rebuild_oracle(state, universe=universe)
            for mode, terms in qs:
                if mode == "and":
                    a, b = ix.search(terms, mode="and"), \
                        iq.conjunctive(oracle, terms)
                elif mode == "or":
                    a, b = ix.search(terms, mode="or"), \
                        iq.disjunctive(oracle, terms)
                else:
                    a = ix.search(terms, mode="topk", k=top_k)
                    b = iq.topk(oracle, terms, top_k, mode="or")
                aa = a if isinstance(a, tuple) else (a,)
                bb = b if isinstance(b, tuple) else (b,)
                assert all(np.array_equal(x, y) for x, y in zip(aa, bb)), \
                    (tag, mode, terms)

        assert_parity(live, "pre-crash")
        crash_at = str(rng.choice(CRASH_POINTS))
        try:
            live.merge(crash_at=crash_at)
            raise AssertionError("injected crash did not fire")
        except CrashPoint:
            pass
        live.close()
        print(f"ingested {len(stream)} ops ({live.counters['acked_ops']} "
              f"acked), crashed merge at {crash_at!r}")

        live = LiveIndex(workdir, fsync=False)  # recovery IS the reopen
        assert_parity(live, f"recovered({crash_at})")
        # retry the merge; queries at every named point stay bit-identical
        live.merge(step_hook=lambda name: assert_parity(
            live, f"mid-merge({name})"))
        assert_parity(live, "post-merge")

        engine = LiveSearchEngine(live, top_k=top_k)
        wl = engine.run_workload(qs)
        # a couple of live writes + a degraded replay check
        doc = int(rng.integers(universe))
        while doc in state:
            doc = int(rng.integers(universe))
        engine.add(doc, {0: 1})
        state[doc] = {0: 1}
        assert_parity(live, "post-workload-write")
        # a plain restart replays the unmerged write and serves it; a
        # query issued *during* replay is flagged degraded("replaying")
        live.close()
        replay_flags = []

        def replay_probe(ix, i, op):
            q = QueryStats()
            ix.search([0], mode="or", stats=q)
            replay_flags.append((q.degraded, list(q.degraded_reasons)))

        live = LiveIndex(workdir, fsync=False, replay_hook=replay_probe)
        assert replay_flags and all(
            d and r == ["replaying"] for d, r in replay_flags), replay_flags
        assert_parity(live, "post-restart")
        stats = {
            "n_ops": len(stream),
            "n_queries": len(qs),
            "crash_point": crash_at,
            "recovered_replayed_ops": live.counters["replayed_ops"],
            "rolled_forward": live.counters["rolled_forward"],
            **{k: wl[k] for k in ("qps", "p50_ms", "p99_ms", "delta_hits",
                                  "tombstones_applied", "doc_count",
                                  "epoch") if k in wl},
        }
        live.close()
        print(f"recovery parity OK at {crash_at!r} + all "
              f"{len(CRASH_POINTS)} mid-merge points — ingest smoke OK")
        if record:
            path = record_benchmark("ingest_smoke", stats)
            print(f"recorded -> {path}")
        return stats
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _repo_benchmarks_path() -> str:
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))  # <repo>/src
    root = os.path.dirname(src) if os.path.basename(src) == "src" else "."
    return os.path.join(root, "experiments", "benchmarks.json")


def record_benchmark(section: str, payload, path: str | None = None):
    """Merge one section into the tracked benchmarks JSON (run.py's format)."""
    path = path or _repo_benchmarks_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    try:
        with open(path) as f:
            merged = json.load(f)
    except (OSError, ValueError):
        merged = {}
    merged[section] = payload
    merged["updated_at"] = time.strftime("%Y-%m-%d %H:%M:%S")
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)
    return path


def serve_engine(cfg, *, requests: int, candidates: int, top_k: int = 10,
                 record: bool = True, seed: int = 0,
                 n_devices: int = 1) -> dict:
    """Build the sharded compressed engine and drive a synthetic workload."""
    import numpy as np

    import jax

    from repro.core import CompressedIntArray
    from repro.models import recsys

    rng = np.random.default_rng(seed)
    params = recsys.init_params(jax.random.PRNGKey(0), cfg)

    n_cand = min(candidates, cfg.n_items - 1)
    cands = np.sort(rng.choice(np.arange(1, cfg.n_items), n_cand,
                               replace=False)).astype(np.uint64)
    corpus = CompressedIntArray.encode(cands, differential=True)
    mesh = serving_mesh(n_devices)
    print(f"corpus: {corpus.n} candidate ids, {corpus.bits_per_int:.2f} "
          f"bits/int ({corpus.compression_ratio:.2f}x vs uint32), "
          f"{corpus.n_blocks} blocks over {n_devices} device(s)")

    engine = ServingEngine(params, cfg, corpus, mesh=mesh, top_k=top_k)
    engine.warmup()

    reqs = [(int(rng.integers(1, max(cfg.n_users, 2))),
             rng.integers(1, cfg.n_items, cfg.seq_len).astype(np.int32))
            for _ in range(requests)]
    stats = engine.run_workload(reqs)
    print(f"served {stats['n_requests']} requests on {stats['n_devices']} "
          f"device(s): {stats['qps']} QPS, "
          f"p50 {stats['p50_ms']} ms, p99 {stats['p99_ms']} ms "
          f"(top-{top_k} of {stats['corpus_n']} compressed candidates)")

    # embedding-bag endpoint smoke (microbatched ragged bags)
    bags = [np.sort(rng.choice(np.arange(1, cfg.n_items),
                               rng.integers(1, cfg.seq_len + 1),
                               replace=False)) for _ in range(5)]
    emb = engine.embed_bags(bags)
    print(f"embedding-bag endpoint: {len(bags)} bags -> {emb.shape}")

    if record:
        path = record_benchmark("serving_engine", stats)
        print(f"recorded -> {path}")
    return stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--devices", type=int, default=0,
                    help="serve on N devices (sharded engine when N > 1): "
                         "the first N attached; on the CPU, N forced "
                         "host-platform devices")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--candidates", type=int, default=1 << 16)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--no-record", action="store_true",
                    help="skip merging engine stats into benchmarks.json")
    ap.add_argument("--metrics-out", default=None, metavar="DIR",
                    help="search arch: capture telemetry over the workload "
                         "and write metrics.prom / trace.jsonl / "
                         "trace-chrome.json to DIR (docs/observability.md)")
    ap.add_argument("--degraded-smoke", action="store_true",
                    help="search arch: kill one logical shard mid-workload "
                         "and assert flagged partial results + healing")
    ap.add_argument("--ingest-smoke", action="store_true",
                    help="search arch: ingest a WAL-backed live index, "
                         "crash the merge at a random point, recover, and "
                         "assert query parity vs a rebuilt index")
    args = ap.parse_args()

    if args.devices:
        # appended LAST so it wins over any inherited duplicate (XLA takes
        # the final occurrence of a repeated flag)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    # jax must initialize AFTER the device-count flag is set
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    n_devices = args.devices or 1
    if args.arch == "search":
        if args.ingest_smoke:
            serve_ingest_smoke(ops=max(args.requests, 50),
                               top_k=args.top_k,
                               record=not args.no_record)
            return
        if args.degraded_smoke:
            serve_search_degraded(queries=args.requests, top_k=args.top_k,
                                  record=not args.no_record,
                                  n_devices=n_devices)
        else:
            serve_search(queries=args.requests, top_k=args.top_k,
                         record=not args.no_record,
                         metrics_out=args.metrics_out, n_devices=n_devices)
        return

    from repro.distributed.api import activate_mesh
    from repro.launch.mesh import make_host_mesh
    from repro.models import registry

    fam = registry.family_of(args.arch)
    cfg = registry.reduced_config(args.arch)
    if fam == "lm":
        with activate_mesh(make_host_mesh()):
            serve_lm(cfg, args.tokens, args.batch)
    elif fam == "recsys":
        if cfg.kind == "two_tower":
            serve_engine(cfg, requests=args.requests,
                         candidates=args.candidates, top_k=args.top_k,
                         record=not args.no_record, n_devices=n_devices)
        else:
            with activate_mesh(make_host_mesh()):
                serve_recsys(cfg, args.batch)
    else:
        raise SystemExit("gnn has no serve step (train-only shapes)")


if __name__ == "__main__":
    main()
