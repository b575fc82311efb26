"""Inverted-index query engine vs a numpy brute-force oracle.

Conjunctive / disjunctive / top-k results must be bit-identical to the
oracle on both formats, dense and banded cores, fused and unfused plans,
single-device and sharded — and the skip-table decode accounting must
prove that blocks whose docid range overlaps no probe are never decoded.
"""
from collections import Counter
from functools import reduce

import numpy as np
import pytest

import jax

from repro.core import CompressedIntArray
from repro.data.synthetic import posting_list, posting_tfs
from repro.index import (QueryStats, build_index, conjunctive, disjunctive,
                         quantize_impacts, topk)
from repro.kernels.vbyte_decode import dispatch, normalize_probe
from repro.kernels.vbyte_decode.dispatch import DecodePlan

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >1 device; run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

FMTS = ["vbyte", "streamvbyte"]
B = 32  # block size (multiple of 4 for streamvbyte)
U = 100_000  # docid universe


def make_lists(rng, sizes, universe=U):
    """Per-term sorted distinct docid lists (ragged vs B on purpose)."""
    return {t: np.sort(rng.choice(universe, size=s, replace=False))
            .astype(np.uint32) for t, s in enumerate(sizes)}


def oracle_and(lists, terms):
    return reduce(np.intersect1d,
                  [lists.get(t, np.zeros(0, np.uint32)) for t in terms]
                  ).astype(np.uint32)


def oracle_or(lists, terms):
    return reduce(np.union1d,
                  [lists.get(t, np.zeros(0, np.uint32)) for t in terms]
                  ).astype(np.uint32)


def oracle_topk(index, lists, terms, k, mode="or"):
    c = Counter()
    for t in dict.fromkeys(terms):
        for d in lists.get(t, ()):
            c[int(d)] += index.impact(t)
    if mode == "and":
        inter = set(oracle_and(lists, terms).tolist())
        c = Counter({d: s for d, s in c.items() if d in inter})
    elif mode == "driver":
        req = set(np.asarray(lists.get(terms[0], ())).tolist())
        c = Counter({d: s for d, s in c.items() if d in req})
    order = sorted(c.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return (np.array([d for d, _ in order], np.uint32),
            np.array([s for _, s in order], np.int32))


def assert_query_matches(index, lists, terms, k=10, **kw):
    np.testing.assert_array_equal(conjunctive(index, terms, **kw),
                                  oracle_and(lists, terms))
    np.testing.assert_array_equal(disjunctive(index, terms, **kw),
                                  oracle_or(lists, terms))
    # TAAT union scoring / constant conjunctive / fused DAAT probing
    for mode in ("or", "and", "driver"):
        ids, scores = topk(index, terms, k, mode=mode, **kw)
        eids, escores = oracle_topk(index, lists, terms, k, mode=mode)
        np.testing.assert_array_equal(ids, eids, err_msg=mode)
        np.testing.assert_array_equal(scores, escores, err_msg=mode)


# ---------------------------------------------------------------------------
# golden vectors
# ---------------------------------------------------------------------------
def test_golden_intersection_union():
    lists = {0: np.array([3, 40, 41, 127, 128, 900, 4000], np.uint32),
             1: np.array([40, 127, 129, 900, 5000], np.uint32),
             2: np.array([1, 40, 900], np.uint32)}
    idx = build_index(lists, block_size=4, n_docs=10_000)
    np.testing.assert_array_equal(
        conjunctive(idx, [0, 1], plan="jnp"),
        np.array([40, 127, 900], np.uint32))
    np.testing.assert_array_equal(
        conjunctive(idx, [0, 1, 2], plan="jnp"),
        np.array([40, 900], np.uint32))
    np.testing.assert_array_equal(
        disjunctive(idx, [1, 2], plan="jnp"),
        np.array([1, 40, 127, 129, 900, 5000], np.uint32))
    # single-term queries are the postings themselves
    np.testing.assert_array_equal(conjunctive(idx, [2], plan="jnp"),
                                  lists[2])
    np.testing.assert_array_equal(disjunctive(idx, [2], plan="jnp"),
                                  lists[2])


# ---------------------------------------------------------------------------
# randomized oracle parity: formats × plans × query widths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("plan", ["fused", "unfused"])
def test_boolean_and_topk_vs_oracle(rng, fmt, plan):
    # ragged sizes (not multiples of B) + a rare term + a dominating term
    lists = make_lists(rng, (45, 300, 701, 1150, 37))
    idx = build_index(lists, format=fmt, block_size=B, n_docs=U)
    for terms in ([1], [0, 3], [4, 1], [0, 1, 2], [0, 1, 2, 3, 4]):
        assert_query_matches(idx, lists, terms, plan=plan)


@pytest.mark.parametrize("fmt", FMTS)
def test_terms_missing_and_empty(rng, fmt):
    lists = make_lists(rng, (60, 200))
    lists[2] = np.zeros(0, np.uint32)  # empty term: one count-0 block
    idx = build_index(lists, format=fmt, block_size=B, n_docs=U)
    assert idx.df(2) == 0 and idx.impact(2) == 0
    assert conjunctive(idx, [0, 2], plan="jnp").size == 0
    assert conjunctive(idx, [0, 99], plan="jnp").size == 0  # unknown term
    np.testing.assert_array_equal(disjunctive(idx, [0, 2, 99], plan="jnp"),
                                  lists[0])
    ids, scores = topk(idx, [0, 2, 99], 5, plan="jnp")
    eids, escores = oracle_topk(idx, lists, [0, 2, 99], 5)
    np.testing.assert_array_equal(ids, eids)
    np.testing.assert_array_equal(scores, escores)


@pytest.mark.parametrize("fmt", FMTS)
def test_topk_ties_deterministic(rng, fmt):
    """Equal dfs ⇒ equal impacts ⇒ exact score ties, broken by docid asc."""
    a = np.sort(rng.choice(U, size=64, replace=False)).astype(np.uint32)
    b = np.sort(rng.choice(U, size=64, replace=False)).astype(np.uint32)
    lists = {0: a, 1: b}
    idx = build_index(lists, format=fmt, block_size=B, n_docs=U)
    assert idx.impact(0) == idx.impact(1)
    for k in (3, 10, 500):  # k < #ties, k within, k > all results
        ids, scores = topk(idx, [0, 1], k, plan="fused")
        eids, escores = oracle_topk(idx, lists, [0, 1], k)
        np.testing.assert_array_equal(ids, eids)
        np.testing.assert_array_equal(scores, escores)
    # repeated query terms must not double-count impacts
    ids, scores = topk(idx, [0, 0, 1], 10, plan="fused")
    eids, escores = oracle_topk(idx, lists, [0, 0, 1], 10)
    np.testing.assert_array_equal(ids, eids)
    np.testing.assert_array_equal(scores, escores)


# ---------------------------------------------------------------------------
# skip-table pruning: decode-count accounting
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", FMTS)
def test_non_overlapping_blocks_never_decoded(fmt):
    # term 1 spans two far-apart clusters; term 0 overlaps only cluster 1,
    # so every cluster-2 block of term 1 must be pruned by the skip table
    t0 = np.arange(100, 800, 3, dtype=np.uint32)
    t1 = np.concatenate([np.arange(0, 1500, 2, dtype=np.uint32),
                         np.arange(60_000, 63_000, 2, dtype=np.uint32)])
    lists = {0: t0, 1: t1}
    idx = build_index(lists, format=fmt, block_size=B, n_docs=U)
    tp1 = idx.terms[1]
    overlapping = int(np.sum((tp1.first_doc <= t0[-1])
                             & (tp1.last_doc >= t0[0])))
    assert overlapping < tp1.n_blocks  # the scenario is non-trivial
    st = QueryStats()
    got = conjunctive(idx, [0, 1], plan="jnp", stats=st)
    np.testing.assert_array_equal(got, oracle_and(lists, [0, 1]))
    # term 1 was probed per chunk: cluster-2 blocks never entered a decode
    assert st.per_term_decoded[1] <= overlapping * \
        (len(t0) // 128 + 1)  # ≤ overlapping blocks per probe chunk
    assert st.blocks_skipped > 0
    # globally disjoint ranges: nothing is decoded at all
    far = {0: np.arange(0, 900, 2, dtype=np.uint32),
           1: np.arange(50_000, 51_000, 2, dtype=np.uint32)}
    idx2 = build_index(far, format=fmt, block_size=B, n_docs=U)
    st2 = QueryStats()
    assert conjunctive(idx2, [0, 1], plan="jnp", stats=st2).size == 0
    assert st2.blocks_decoded == 0 and st2.decode_calls == 0


def test_topk_skip_accounting(rng):
    lists = make_lists(rng, (50, 900))
    idx = build_index(lists, block_size=B, n_docs=U)
    st = QueryStats()
    ids, scores = topk(idx, [0, 1], 10, mode="driver", plan="jnp", stats=st)
    total = st.blocks_decoded + st.blocks_skipped
    assert total > 0 and st.blocks_skipped > 0
    assert st.ints_decoded > 0 and st.decode_calls > 0
    # DAAT scores genuinely vary: driver docs in both terms outrank
    # driver-only docs (non-constant expected output for the bm25 path)
    eids, escores = oracle_topk(idx, lists, [0, 1], 10, mode="driver")
    np.testing.assert_array_equal(ids, eids)
    np.testing.assert_array_equal(scores, escores)


# ---------------------------------------------------------------------------
# plan-space parity: Pallas kernel, dense vs banded cores
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", FMTS)
def test_kernel_plan_parity(rng, fmt):
    lists = make_lists(rng, (40, 180), universe=4000)
    idx = build_index(lists, format=fmt, block_size=B, n_docs=4000)
    for terms in ([0, 1], [1]):
        np.testing.assert_array_equal(
            conjunctive(idx, terms, plan="kernel", probe_width=64),
            conjunctive(idx, terms, plan="jnp", probe_width=64))
    for mode in ("or", "and", "driver"):
        ids_k, sc_k = topk(idx, [0, 1], 7, mode=mode, plan="kernel",
                           probe_width=64)
        ids_j, sc_j = topk(idx, [0, 1], 7, mode=mode, plan="jnp",
                           probe_width=64)
        np.testing.assert_array_equal(ids_k, ids_j, err_msg=mode)
        np.testing.assert_array_equal(sc_k, sc_j, err_msg=mode)


@pytest.mark.parametrize("fmt", FMTS)
def test_dense_vs_banded_cores(rng, fmt):
    lists = make_lists(rng, (90, 800, 350))
    idx = build_index(lists, format=fmt, block_size=B, n_docs=U)
    # the jnp decoder against the Pallas tile core, which for Stream VByte
    # is its banded route
    dense = DecodePlan("jnp", True)
    banded = DecodePlan("pallas", True,
                        chunk=16 if fmt == "streamvbyte" else None)
    for terms in ([0, 1], [0, 1, 2]):
        np.testing.assert_array_equal(
            conjunctive(idx, terms, plan=dense),
            conjunctive(idx, terms, plan=banded))
        ids_d, sc_d = topk(idx, terms, 9, plan=dense)
        ids_b, sc_b = topk(idx, terms, 9, plan=banded)
        np.testing.assert_array_equal(ids_d, ids_b)
        np.testing.assert_array_equal(sc_d, sc_b)
        np.testing.assert_array_equal(conjunctive(idx, terms, plan=dense),
                                      oracle_and(lists, terms))


# ---------------------------------------------------------------------------
# the epilogues themselves (all plans, count-0 blocks, probe padding)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", FMTS)
def test_membership_bm25_epilogue_parity(rng, fmt):
    vals = np.sort(rng.choice(3000, size=2 * B + 7, replace=False)
                   ).astype(np.uint64)
    arr = CompressedIntArray.encode(vals, format=fmt, block_size=B,
                                    differential=True)
    ops = {k: np.pad(np.asarray(v),
                     ((0, 2),) + ((0, 0),) * (np.asarray(v).ndim - 1))
           for k, v in arr.device_operands().items()}  # + 2 count-0 blocks
    probe_ids = np.sort(rng.choice(3000, size=50, replace=False))
    probe = normalize_probe(probe_ids, 64)
    assert probe.shape == (1, 64) and (probe[0, 50:] == -1).all()
    outs = {}
    for plan in ("kernel", "jnp", "unfused"):
        outs[plan] = np.asarray(dispatch.decode(
            ops, format=fmt, block_size=B, differential=True,
            epilogue="membership", epilogue_operands={"probe": probe},
            plan=plan))
    for plan, o in outs.items():
        np.testing.assert_array_equal(o, outs["kernel"], err_msg=plan)
    hits = outs["jnp"].any(axis=0)[:50]
    np.testing.assert_array_equal(
        hits.astype(bool), np.isin(probe_ids, vals.astype(np.int64)))
    assert not outs["jnp"][:, 50:].any()  # pad probes never match
    for plan in ("kernel", "jnp", "unfused"):
        sc = np.asarray(dispatch.decode(
            ops, format=fmt, block_size=B, differential=True,
            epilogue="bm25_accum",
            epilogue_operands={"probe": probe,
                               "impact": np.asarray([[11]], np.int32)},
            plan=plan))
        np.testing.assert_array_equal(
            sc.sum(axis=0)[:50], hits[:50].astype(np.int32) * 11,
            err_msg=plan)


def test_normalize_probe_validation():
    with pytest.raises(ValueError, match="sorted"):
        normalize_probe(np.array([5, 3]), 8)
    with pytest.raises(ValueError, match="width"):
        normalize_probe(np.arange(9), 8)
    with pytest.raises(ValueError, match="2\\^31"):
        normalize_probe(np.array([1 << 31], np.int64), 8)
    out = normalize_probe(np.zeros(0, np.uint32), 4)
    assert (out == -1).all()


# ---------------------------------------------------------------------------
# builder + slice_blocks
# ---------------------------------------------------------------------------
def test_builder_validation_and_stats(rng):
    with pytest.raises(ValueError, match="strictly increasing"):
        build_index({0: np.array([4, 4, 5], np.uint32)})
    with pytest.raises(ValueError, match="2\\^31"):
        build_index({0: np.array([1 << 31], np.uint64)})
    lists = make_lists(rng, (70, 300))
    idx = build_index(lists, block_size=B, n_docs=U)
    s = idx.stats()
    assert s["n_terms"] == 2 and s["n_postings"] == 370
    assert 0 < idx.bits_per_int <= 40
    assert idx.impact(0) > idx.impact(1) > 0  # rarer term scores higher
    tp = idx.terms[1]
    assert tp.n_blocks == -(-300 // B)
    np.testing.assert_array_equal(tp.first_doc[0], lists[1][0])
    np.testing.assert_array_equal(tp.last_doc[-1], lists[1][-1])


@pytest.mark.parametrize("fmt", FMTS)
def test_slice_blocks_decode(rng, fmt):
    vals = np.sort(rng.choice(U, size=5 * B + 11, replace=False)
                   ).astype(np.uint64)
    arr = CompressedIntArray.encode(vals, format=fmt, block_size=B,
                                    differential=True)
    sub = arr.slice_blocks(2, 5)
    np.testing.assert_array_equal(sub.decode(plan="jnp").astype(np.uint64),
                                  vals[2 * B: 5 * B])
    # tail slice (ragged last block) + count-0 padding blocks
    sub = arr.slice_blocks(4, 6, pad_to=4)
    assert sub.n_blocks == 4 and sub.n == B + 11
    np.testing.assert_array_equal(sub.decode(plan="jnp").astype(np.uint64),
                                  vals[4 * B:])
    # non-contiguous gather with the partial block FIRST: decode() must
    # concatenate valid prefixes per block, not flat-trim to n
    sub = arr.take_blocks([5, 0])
    np.testing.assert_array_equal(
        sub.decode(plan="jnp").astype(np.uint64),
        np.concatenate([vals[5 * B:], vals[:B]]))


# ---------------------------------------------------------------------------
# synthetic posting lists (satellite: long lists + uint32 contract)
# ---------------------------------------------------------------------------
def test_posting_list_dtype_and_short(rng):
    ids = posting_list(rng, 500, universe=10_000)
    assert ids.dtype == np.uint32 and len(ids) == 500
    assert np.all(np.diff(ids.astype(np.int64)) > 0)


def test_posting_list_long_sorted_gap_path(rng):
    n = 1 << 22  # the length that used to raise ValueError("list too long")
    ids = posting_list(rng, n, universe=1 << 23)
    assert ids.dtype == np.uint32 and len(ids) == n
    d = np.diff(ids.astype(np.int64))
    assert d.min() >= 1  # strictly increasing ⇒ distinct
    assert int(ids[-1]) < 1 << 23
    # degenerate: length == universe
    full = posting_list(rng, 16, universe=16)
    np.testing.assert_array_equal(full, np.arange(16, dtype=np.uint32))


# ---------------------------------------------------------------------------
# per-posting impacts + block-max pruned top-k (mode="maxscore")
# ---------------------------------------------------------------------------
def make_tfs(rng, lists):
    return {t: posting_tfs(rng, len(v)) for t, v in lists.items()}


def oracle_topk_weighted(index, lists, tfs, terms, k):
    """Weighted TAAT oracle: per-posting quantized impacts, numpy only."""
    c = Counter()
    for t in dict.fromkeys(terms):
        docs = lists.get(t)
        if docs is None or len(docs) == 0 or t not in index:
            continue
        tf = tfs.get(t, np.ones(len(docs), np.int64))
        q = quantize_impacts(index.impact(t), tf, index.impact_bits)
        for d, s in zip(docs, q):
            c[int(d)] += int(s)
    order = sorted(c.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return (np.array([d for d, _ in order], np.uint32),
            np.array([s for _, s in order], np.int32))


def test_impacts_stream_roundtrip_and_block_max(rng):
    lists = make_lists(rng, (40, 500))
    tfs = make_tfs(rng, lists)
    idx = build_index(lists, tfs=tfs, block_size=B, n_docs=U)
    assert idx.has_tf and idx.stats()["has_tf"]
    for t, docs in lists.items():
        tp = idx.terms[t]
        q = quantize_impacts(idx.impact(t), tfs[t], idx.impact_bits)
        np.testing.assert_array_equal(
            tp.impacts.decode(plan="jnp").astype(np.int32), q)
        # impacts blocks align 1:1 with the docid blocks
        assert tp.impacts.n_blocks == tp.n_blocks
        assert tp.impacts.block_size == tp.arr.block_size
        nb = tp.n_blocks
        want = [int(q[b * B:(b + 1) * B].max()) for b in range(nb)]
        np.testing.assert_array_equal(tp.max_impact, want)
        assert tp.ub == max(want)
    # tf-free build degenerates to the constant impact (sat(1) == 1)
    plain = build_index(lists, block_size=B, n_docs=U)
    assert not plain.has_tf
    for t in lists:
        tp = plain.terms[t]
        assert (tp.impacts.decode(plan="jnp") == plain.impact(t)).all()
        assert tp.ub == plain.impact(t)


def test_topk_k_validation(rng):
    lists = make_lists(rng, (30, 60))
    idx = build_index(lists, block_size=B, n_docs=U)
    for bad in (0, -1, -7, 1.5, 2.0, True, False, "3", None):
        with pytest.raises(ValueError, match="positive integer"):
            topk(idx, [0, 1], bad)
    # numpy integers are fine (np.argmax etc. produce them)
    ids, _ = topk(idx, [0, 1], np.int64(3), plan="jnp")
    assert ids.size == 3


def test_builder_rejects_non_integer_inputs(rng):
    with pytest.raises(ValueError, match="integer dtype"):
        build_index({0: np.array([1.0, 2.0, 4.0])})
    with pytest.raises(ValueError, match="integer dtype"):
        build_index({0: np.array([1, 2], np.uint32)},
                    tfs={0: np.array([1.0, 2.0])})
    with pytest.raises(ValueError, match="non-negative"):
        build_index({0: np.array([-3, 5], np.int64)})
    with pytest.raises(ValueError, match="≥ 1"):
        build_index({0: np.array([1, 2], np.uint32)},
                    tfs={0: np.array([0, 2])})
    with pytest.raises(ValueError, match="length"):
        build_index({0: np.array([1, 2], np.uint32)},
                    tfs={0: np.array([1, 2, 3])})


@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("plan", ["fused", "unfused"])
def test_maxscore_vs_oracle(rng, fmt, plan):
    lists = make_lists(rng, (45, 300, 701, 1150, 37))
    tfs = make_tfs(rng, lists)
    idx = build_index(lists, tfs=tfs, format=fmt, block_size=B, n_docs=U)
    for terms in ([1], [0, 3], [4, 1], [0, 1, 2], [0, 1, 2, 3, 4]):
        for k in (1, 3, 10, 100):
            ids, scores = topk(idx, terms, k, mode="maxscore", plan=plan)
            eids, escores = oracle_topk_weighted(idx, lists, tfs, terms, k)
            msg = f"terms={terms} k={k}"
            np.testing.assert_array_equal(ids, eids, err_msg=msg)
            np.testing.assert_array_equal(scores, escores, err_msg=msg)
            # and bit-identical to the exhaustive TAAT mode
            oids, oscores = topk(idx, terms, k, mode="or", plan=plan)
            np.testing.assert_array_equal(ids, oids, err_msg=msg)
            np.testing.assert_array_equal(scores, oscores, err_msg=msg)


@pytest.mark.parametrize("fmt", FMTS)
def test_maxscore_ties_and_k_beyond_candidates(rng, fmt):
    """tf-free index (all impacts equal per term): exact ties break by
    docid ascending under maxscore exactly as under TAAT."""
    a = np.sort(rng.choice(U, size=64, replace=False)).astype(np.uint32)
    b = np.sort(rng.choice(U, size=64, replace=False)).astype(np.uint32)
    lists = {0: a, 1: b}
    idx = build_index(lists, format=fmt, block_size=B, n_docs=U)
    for k in (3, 10, 500):  # k < #ties, k within, k > all candidates
        ids, scores = topk(idx, [0, 1], k, mode="maxscore", plan="fused")
        eids, escores = oracle_topk(idx, lists, [0, 1], k)
        np.testing.assert_array_equal(ids, eids)
        np.testing.assert_array_equal(scores, escores)
    # repeated query terms must not double-count impacts
    ids, scores = topk(idx, [0, 0, 1], 10, mode="maxscore", plan="fused")
    eids, escores = oracle_topk(idx, lists, [0, 0, 1], 10)
    np.testing.assert_array_equal(ids, eids)
    np.testing.assert_array_equal(scores, escores)


@pytest.mark.parametrize("fmt", FMTS)
def test_maxscore_seed_path_parity_and_pruning(rng, fmt):
    """Selective shape (tiny saturated term + long tf=1 lists) exercises
    the seed phase: the tiny list is decoded up front, θ matures past the
    long terms' upper bounds before they ever stream, and every long
    block not gathered by a candidate probe is threshold-pruned — never
    decoded by any pass (the long lists carry tf=1 so their bounds sit
    strictly under θ; saturated tfs would ceiling them at the quantizer
    max and erase the selective gap)."""
    lists = {0: np.sort(rng.choice(U, 40, replace=False)).astype(np.uint32),
             1: np.sort(rng.choice(U, 1500, replace=False)).astype(np.uint32),
             2: np.sort(rng.choice(U, 2000, replace=False)).astype(np.uint32)}
    tfs = {0: np.full(40, 50, np.int64),  # saturated: rare term dominates
           1: np.ones(1500, np.int64), 2: np.ones(2000, np.int64)}
    idx = build_index(lists, tfs=tfs, format=fmt, block_size=B, n_docs=U)
    # seed phase requires a strip-sized term next to a much longer one,
    # and pruning requires the long terms' combined bound under θ
    strip_blocks = 64 // B
    assert idx.terms[0].n_blocks <= strip_blocks
    assert idx.terms[2].n_blocks > 4 * strip_blocks
    assert idx.terms[1].ub + idx.terms[2].ub < idx.terms[0].ub
    st = QueryStats()
    ids, scores = topk(idx, [0, 1, 2], 10, mode="maxscore", plan="fused",
                       probe_width=64, stats=st)
    eids, escores = oracle_topk_weighted(idx, lists, tfs, [0, 1, 2], 10)
    np.testing.assert_array_equal(ids, eids)
    np.testing.assert_array_equal(scores, escores)
    # the seed term was fully decoded; the long lists were partly pruned
    assert st.per_term_decoded[0] >= idx.terms[0].n_blocks
    assert st.blocks_pruned > 0 and st.postings_pruned > 0
    assert st.impact_ints_decoded > 0  # weighted epilogues actually ran
    # pruned/decoded block sets partition each term exactly: a block is
    # threshold-pruned iff NO pass (strip pull, probe, merge) decoded it
    for t, tp in idx.terms.items():
        got = len(st.per_term_blocks.get(t, ()))
        assert st.per_term_pruned.get(t, 0) + got == tp.n_blocks


def test_maxscore_all_blocks_pruned_zero_decode(rng):
    """Docid-disjoint long term whose upper bound is under θ: every one of
    its blocks is threshold-pruned and none is ever decoded."""
    rare = np.sort(rng.choice(3000, 30, replace=False)).astype(np.uint32)
    heavy = np.sort(50_000 + rng.choice(50_000, 2000, replace=False)
                    ).astype(np.uint32)
    lists = {0: rare, 1: heavy}
    tfs = {0: np.full(30, 50, np.int64), 1: np.ones(2000, np.int64)}
    idx = build_index(lists, tfs=tfs, block_size=B, n_docs=U)
    st = QueryStats()
    ids, scores = topk(idx, [0, 1], 3, mode="maxscore", plan="fused",
                       probe_width=64, stats=st)
    eids, escores = oracle_topk_weighted(idx, lists, tfs, [0, 1], 3)
    np.testing.assert_array_equal(ids, eids)
    np.testing.assert_array_equal(scores, escores)
    # scenario precondition: the heavy term alone cannot reach the top-3
    assert idx.terms[1].ub <= int(escores[-1])
    tp1 = idx.terms[1]
    assert st.per_term_decoded.get(1, 0) == 0
    assert st.blocks_pruned == tp1.n_blocks
    assert st.postings_pruned == len(heavy)
    # only the rare seed term's postings (and impacts) were ever decoded
    assert st.ints_decoded == len(rare)


@pytest.mark.parametrize("fmt", FMTS)
def test_maxscore_threshold_tie_parity(fmt):
    """A candidate whose exact score TIES the running θ at a smaller
    docid than the tied incumbent must still be returned first — every
    MaxScore bound comparison has to be strict, else the non-essential
    split / block prune / probe dead-check silently drops it.

    Engineered shape: a tiny seed term puts incumbent D (large docid)
    into the heap with score θ; a long-list doc d* < D, sharing no term
    with the seed, has tfs tuned so its exact score equals θ. The final
    (score desc, docid asc) order must rank d* ahead of D."""
    U2 = 100_000
    d_star, D = 50, 90_000
    t1 = np.unique(np.concatenate(
        [np.arange(100, 8100, 4), [d_star, D]])).astype(np.uint32)
    t2 = np.unique(np.concatenate(
        [np.arange(102, 8102, 4), [d_star]])).astype(np.uint32)
    t0 = np.array([D, 90_050, 90_100], np.uint32)
    lists = {0: t0, 1: t1, 2: t2}
    probe = build_index(lists, block_size=B, n_docs=U2)
    b0, b1, b2 = (probe.impact(t) for t in (0, 1, 2))
    # quantized impacts reachable from integer tfs, per term
    def reach(base):
        out = {}
        for tf in range(1, 401):
            out.setdefault(int(quantize_impacts(base, [tf])[0]), tf)
        return out
    r1, r2 = reach(b1), reach(b2)
    # tie construction: θ = score(D) = b0 + q1D  ==  qa + qb = score(d*)
    found = next(((q1D, qa, b0 + q1D - qa) for q1D in sorted(r1)
                  for qa in sorted(r1) if b0 + q1D - qa in r2), None)
    assert found, "no exact tie constructible from these impact bases"
    q1D, qa, qb = found
    tf1 = np.ones(t1.size, np.int64)
    tf1[np.searchsorted(t1, D)] = r1[q1D]
    tf1[np.searchsorted(t1, d_star)] = r1[qa]
    tf2 = np.ones(t2.size, np.int64)
    tf2[np.searchsorted(t2, d_star)] = r2[qb]
    tfs = {0: np.ones(3, np.int64), 1: tf1, 2: tf2}
    idx = build_index(lists, tfs=tfs, format=fmt, block_size=B, n_docs=U2)
    # seed-phase preconditions: t0 is strip-sized next to long lists
    assert idx.terms[0].n_blocks <= 64 // B
    assert idx.terms[1].n_blocks > 4 * (64 // B)
    theta = b0 + q1D
    for k in (1, 2, 3, 5):
        ids, scores = topk(idx, [0, 1, 2], k, mode="maxscore",
                           plan="fused", probe_width=64)
        eids, escores = oracle_topk_weighted(idx, lists, tfs, [0, 1, 2], k)
        np.testing.assert_array_equal(ids, eids, err_msg=f"k={k}")
        np.testing.assert_array_equal(scores, escores, err_msg=f"k={k}")
    # the tie really exists and resolves toward the smaller docid
    ids, scores = topk(idx, [0, 1, 2], 2, mode="maxscore", plan="fused",
                       probe_width=64)
    np.testing.assert_array_equal(ids, [d_star, D])
    np.testing.assert_array_equal(scores, [theta, theta])


def test_maxscore_pruned_accounting_partition(rng):
    """Dense-overlap workload (nearly every block of every term ends up
    decoded by some pass): a block gathered by a non-essential
    probe/merge pass is NOT threshold-pruned even though the strip cursor
    never reached it, so per term the pruned/decoded block sets partition
    the list exactly and ``blocks_pruned + unique-decoded == total`` —
    the old accounting double-booked probe-decoded blocks as pruned
    (decoded + pruned exceeded the whole index)."""
    lists = make_lists(rng, (40, 1500, 2000))
    tfs = make_tfs(rng, lists)  # zipf tfs saturate the quantizer: the
    #   long terms' bounds tie θ, so nothing is strictly prunable
    idx = build_index(lists, tfs=tfs, block_size=B, n_docs=U)
    st = QueryStats()
    ids, scores = topk(idx, [0, 1, 2], 10, mode="maxscore", plan="jnp",
                       probe_width=64, stats=st)
    oids, oscores = topk(idx, [0, 1, 2], 10, mode="or", plan="jnp")
    np.testing.assert_array_equal(ids, oids)
    np.testing.assert_array_equal(scores, oscores)
    total_blocks = sum(tp.n_blocks for tp in idx.terms.values())
    for t, tp in idx.terms.items():
        got = len(st.per_term_blocks.get(t, ()))
        assert st.per_term_pruned.get(t, 0) + got == tp.n_blocks
    # pruned and decoded are disjoint, so pruned can never exceed the
    # index minus what was decoded (the old accounting double-booked
    # probe-decoded blocks as pruned: decoded + pruned > total)
    uniq_decoded = sum(len(s) for s in st.per_term_blocks.values())
    assert st.blocks_pruned + uniq_decoded == total_blocks


def test_probe_rows_accounting(rng):
    """Row-gathered probe passes count per-probe row gathers separately
    from the unique decoded/skipped block partition, and ints follow
    rows (the real decode work), not unique blocks."""
    lists = make_lists(rng, (40, 1200))
    idx = build_index(lists, block_size=B, n_docs=U)
    st = QueryStats()
    got = conjunctive(idx, [0, 1], plan="jnp", stats=st)
    np.testing.assert_array_equal(got, oracle_and(lists, [0, 1]))
    tp1 = idx.terms[1]
    # driver decode pass + probe pass both account term 1's blocks once
    assert st.rows_gathered > 0
    # every gathered row decodes a nonempty block
    assert st.ints_decoded >= st.rows_gathered
    # unique blocks considered per pass never exceed the term's total
    assert st.per_term_decoded[1] <= tp1.n_blocks


def test_search_engine_maxscore_mode(rng):
    from repro.launch.serve import SearchEngine

    lists = make_lists(rng, (50, 600, 900))
    tfs = make_tfs(rng, lists)
    idx = build_index(lists, tfs=tfs, block_size=B, n_docs=U)
    engine = SearchEngine(idx, top_k=5)
    for terms in ([0, 1], [0, 1, 2]):
        ids_m, sc_m = engine.search(terms, "topk_maxscore")
        ids_t, sc_t = engine.search(terms, "topk")
        np.testing.assert_array_equal(ids_m, ids_t)
        np.testing.assert_array_equal(sc_m, sc_t)
    stats = engine.run_workload([("topk_maxscore", [0, 1, 2]),
                                 ("topk", [0, 2])])
    assert {"pruned_block_rate", "pruned_impact_rate"} <= stats.keys()
    assert 0 <= stats["pruned_block_rate"] <= 1
    assert 0 <= stats["pruned_impact_rate"] <= 1


# ---------------------------------------------------------------------------
# SearchEngine: workload driver + sharded parity
# ---------------------------------------------------------------------------
def test_search_engine_workload(rng):
    from repro.launch.serve import SearchEngine, search_queries

    lists = make_lists(rng, (60, 250, 400))
    idx = build_index(lists, block_size=B, n_docs=U)
    engine = SearchEngine(idx, top_k=5)
    qs = search_queries(rng, idx, 9)
    engine.warmup(qs[:3])
    stats = engine.run_workload(qs)
    assert stats["n_queries"] == 9 and stats["qps"] > 0
    assert stats["blocks_decoded"] > 0
    assert 0 <= stats["block_skip_rate"] <= 1
    np.testing.assert_array_equal(engine.search([0, 1], "and"),
                                  oracle_and(lists, [0, 1]))


@multi_device
@pytest.mark.parametrize("fmt", FMTS)
def test_sharded_vs_single_parity(rng, fmt):
    """Sharded engine (block-parallel shard_map, no skip slicing) must be
    bit-identical to the single-device skip-pruned engine."""
    from repro.launch.serve import SearchEngine

    lists = make_lists(rng, (45, 300, 700))
    idx = build_index(lists, format=fmt, block_size=B, n_docs=U)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    single = SearchEngine(idx, top_k=8)
    sharded = SearchEngine(idx, mesh=mesh, top_k=8)
    assert not sharded.use_skip
    for terms in ([0, 1], [0, 1, 2]):
        np.testing.assert_array_equal(sharded.search(terms, "and"),
                                      single.search(terms, "and"))
        np.testing.assert_array_equal(sharded.search(terms, "or"),
                                      single.search(terms, "or"))
        for mode in ("topk", "topk_driver"):
            ids_s, sc_s = sharded.search(terms, mode)
            ids_1, sc_1 = single.search(terms, mode)
            np.testing.assert_array_equal(ids_s, ids_1, err_msg=mode)
            np.testing.assert_array_equal(sc_s, sc_1, err_msg=mode)
        np.testing.assert_array_equal(ids_1,
                                      oracle_topk(idx, lists, terms, 8,
                                                  mode="driver")[0])
