"""Robustness property tests: detect-or-defined-value (docs/robustness.md).

The contract, per corruption class × format × vectorized plan: a corrupted
stream is either **detected** — a typed :class:`DecodeError` subclass with
block/term coordinates from the validators or the checksum-verified decode
— or **provably harmless** — every plan decodes it to the same defined
value (no crash, dense and banded bit-identical), so the serving layer can
degrade instead of dying. With the checksum column present, *every*
corruption class must land on the detected side.

Also covers: encode-time input validation (satellite of the same PR),
checksum survival through ``take_blocks``/``slice_blocks`` and the pytree
protocol, deadline-degraded query semantics, and the hardened
``SearchEngine`` paths (retry, quarantine, bound fallback, shard loss).
"""
import dataclasses

import numpy as np
import pytest

from conftest import make_valid_stream

from repro.core import CompressedIntArray
from repro.core.vbyte import encode as venc
from repro.core.vbyte import stream_vbyte as svb
from repro.index import QueryStats, build_index, conjunctive, disjunctive, topk
from repro.kernels.vbyte_decode import dispatch
from repro.robustness import (BlockMetaError, BoundViolationError,
                              ChecksumError, Deadline, DecodeError,
                              decode_checked, validate_array, validate_meta)
from repro.robustness import faultgen
from repro.robustness.validate import expected_checksums

FORMATS = ("vbyte", "streamvbyte", "binpack")
PLANS = ("jnp", "kernel")  # the grid plans: jnp decoder, Pallas tile core
SEEDS = (0, 1, 2)


def _clean_array(fmt, *, n=200, block_size=64, differential=False,
                 checksum=True, seed=0):
    rng = np.random.default_rng(seed)
    vals = make_valid_stream(rng, n,
                             max_bits=30 if fmt == "streamvbyte" else 32)
    if differential:
        vals = np.cumsum(vals % 997).astype(np.uint64)  # sorted, in-range
    return CompressedIntArray.encode(vals, format=fmt, block_size=block_size,
                                     differential=differential,
                                     checksum=checksum)


# ---------------------------------------------------------------------------
# encode-time input validation (core/vbyte/encode.py, stream_vbyte.py)
# ---------------------------------------------------------------------------
class TestEncodeValidation:
    @pytest.mark.parametrize("fmt", FORMATS)
    def test_negative_rejected(self, fmt):
        with pytest.raises(ValueError, match="non-negative"):
            CompressedIntArray.encode(np.array([3, -1, 5]), format=fmt)

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_out_of_range_rejected(self, fmt):
        with pytest.raises(ValueError, match="2\\^32"):
            CompressedIntArray.encode(np.array([1, 2**32], np.uint64),
                                      format=fmt)

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_float_rejected(self, fmt):
        with pytest.raises(ValueError, match="integer"):
            CompressedIntArray.encode(np.array([1.5, 2.5]), format=fmt)

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_wrap_escape_hatch(self, fmt):
        # wrap=True is the explicit opt-in: values wrap mod 2^32
        vals = np.array([-1, 2**32 + 5, 7], np.object_).astype(np.int64)
        arr = CompressedIntArray.encode(vals, format=fmt, wrap=True)
        np.testing.assert_array_equal(
            arr.decode(), np.array([2**32 - 1, 5, 7], np.uint32))

    def test_stream_encoders_validate(self):
        for enc in (venc.encode_stream, svb.encode_stream):
            with pytest.raises(ValueError, match="non-negative"):
                enc(np.array([-2]))
            enc(np.array([-2]), wrap=True)  # escape hatch

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_ragged_lists_validated_with_coordinates(self, fmt):
        with pytest.raises(ValueError, match="list 1"):
            CompressedIntArray.encode_ragged([[1, 2], [3, -4]], format=fmt)

    def test_error_message_names_the_fix(self):
        with pytest.raises(ValueError, match="wrap=True"):
            venc.encode_blocked(np.array([-1]))


# ---------------------------------------------------------------------------
# checksum column: round-trip, epilogue parity, block ops, pytree
# ---------------------------------------------------------------------------
class TestChecksum:
    @pytest.mark.parametrize("fmt", FORMATS)
    @pytest.mark.parametrize("differential", (False, True))
    def test_column_matches_scalar_recompute(self, fmt, differential):
        arr = _clean_array(fmt, differential=differential)
        np.testing.assert_array_equal(arr.checksums, expected_checksums(arr))

    @pytest.mark.parametrize("fmt", FORMATS)
    @pytest.mark.parametrize("plan", PLANS)
    def test_checked_decode_bit_exact_with_unchecked(self, fmt, plan):
        arr = _clean_array(fmt)
        grid = decode_checked(arr, plan=plan)
        ref = np.asarray(arr.decode_blocked(plan=plan))
        np.testing.assert_array_equal(grid, ref.astype(np.uint32))

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_checksum_epilogue_fused_unfused_parity(self, fmt):
        arr = _clean_array(fmt)
        _, cs_f = dispatch.decode(arr, epilogue="checksum", plan="fused")
        _, cs_u = dispatch.decode(arr, epilogue="checksum", plan="unfused")
        np.testing.assert_array_equal(np.asarray(cs_f), np.asarray(cs_u))

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_take_and_slice_blocks_carry_checksums(self, fmt):
        arr = _clean_array(fmt, n=500, block_size=64)
        sub = arr.take_blocks(np.array([5, 1, 3]))
        np.testing.assert_array_equal(
            sub.checksums, np.asarray(arr.checksums)[[5, 1, 3]])
        decode_checked(sub, plan="jnp")  # still verifies
        sl = arr.slice_blocks(2, 6, pad_to=8)
        assert np.asarray(sl.checksums).shape[0] == 8
        assert not np.asarray(sl.checksums)[4:].any()  # pad blocks -> 0
        decode_checked(sl, plan="jnp")

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_pytree_roundtrip_drops_checksums_like_host_enc(self, fmt):
        import jax

        arr = _clean_array(fmt)
        leaves, treedef = jax.tree_util.tree_flatten(arr)
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        # off-tree host metadata: dropped inside jit/shard_map, and the
        # unchecked decode of the rebuilt array is unchanged
        assert back.checksums is None
        np.testing.assert_array_equal(np.asarray(back.decode_blocked()),
                                      np.asarray(arr.decode_blocked()))

    def test_decode_checked_requires_column(self):
        arr = _clean_array("vbyte", checksum=False)
        with pytest.raises(ValueError, match="checksum=True"):
            decode_checked(arr)

    def test_builder_threads_checksum_to_both_streams(self):
        rng = np.random.default_rng(0)
        docs = np.unique(rng.integers(0, 1 << 20, 400))
        index = build_index({0: docs}, tfs={0: 1 + (np.arange(docs.size) % 5)},
                            n_docs=1 << 20, checksum=True)
        tp = index.terms[0]
        assert tp.arr.checksums is not None
        assert tp.impacts.checksums is not None
        decode_checked(tp.arr, plan="jnp")
        decode_checked(tp.impacts, plan="jnp")


# ---------------------------------------------------------------------------
# the fuzz contract: every corruption class is detect-or-defined-value
# ---------------------------------------------------------------------------
def _detect(arr, term=None):
    """Run the full detection stack; return the typed error or None."""
    try:
        validate_array(arr, term=term)
        if arr.checksums is not None:
            decode_checked(arr, plan="jnp", term=term)
        return None
    except DecodeError as e:
        return e


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("cls", sorted(faultgen.STREAM_CLASSES))
@pytest.mark.parametrize("seed", SEEDS)
def test_corruption_detected_with_checksums(fmt, cls, seed):
    """With the checksum column present, every applicable corruption class
    must be *detected* — a typed DecodeError carrying coordinates."""
    differential = cls == "base_corrupt"
    arr = _clean_array(fmt, differential=differential, seed=seed)
    c = faultgen.corrupt(arr, cls, seed)
    if c is None:
        pytest.skip(f"{cls} does not apply to {fmt}")
    err = _detect(c.arr, term=42)
    assert isinstance(err, DecodeError), (cls, c.detail)
    assert err.term == 42 or err.block is not None, (cls, str(err))
    # and the clean twin still passes: detection is not a false positive
    assert _detect(arr, term=42) is None


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("cls", sorted(faultgen.STREAM_CLASSES))
@pytest.mark.parametrize("seed", SEEDS)
def test_corruption_detect_or_defined_without_checksums(fmt, cls, seed):
    """Without checksums, a corruption that slips past the host validators
    must decode to the same *defined* value on every vectorized plan."""
    differential = cls == "base_corrupt"
    arr = _clean_array(fmt, differential=differential, checksum=False,
                       seed=seed)
    c = faultgen.corrupt(arr, cls, seed)
    if c is None:
        pytest.skip(f"{cls} does not apply to {fmt} without checksums")
    if _detect(c.arr) is not None:
        return  # detected: the strong outcome
    grids = [np.asarray(c.arr.decode_blocked(plan=p)) for p in PLANS]
    np.testing.assert_array_equal(grids[0], grids[1])
    # the scalar oracle agrees on the valid prefix, too: defined garbage,
    # identical everywhere — serving can quarantine and move on
    flat = c.arr.decode(plan=PLANS[0])
    assert flat.shape == (c.arr.n,) and flat.dtype == np.uint32


@pytest.mark.parametrize("cls", sorted(faultgen.INDEX_CLASSES))
def test_index_corruption_detected(cls):
    rng = np.random.default_rng(0)
    docs = np.unique(rng.integers(0, 1 << 20, 600))
    index = build_index({7: docs}, tfs={7: 1 + (np.arange(docs.size) % 7)},
                        n_docs=1 << 20, checksum=True)
    tp = faultgen.INDEX_CLASSES[cls](index.terms[7], seed=3)
    with pytest.raises(DecodeError) as ei:
        validate_meta(tp, deep=True)
        if tp.impacts is not None:
            decode_checked(tp.impacts, plan="jnp", term=7)
    if cls == "max_impact_under":
        assert isinstance(ei.value, BoundViolationError)
    assert ei.value.term == 7 or ei.value.block is not None


def test_single_value_corruption_always_caught():
    """The odd positional weights are invertible mod 2^32: ANY single-slot
    delta shifts the checksum. Exhaustively perturb every slot."""
    arr = _clean_array("vbyte", n=16, block_size=8)
    grid = np.asarray(arr.decode_blocked(plan="jnp")).astype(np.uint64)
    counts = np.asarray(arr.counts)
    from repro.core.compressed_array import block_checksums

    clean = block_checksums(grid, counts)
    rng = np.random.default_rng(0)
    for b in range(grid.shape[0]):
        for j in range(int(counts[b])):
            g = grid.copy()
            g[b, j] ^= np.uint64(1) << np.uint64(rng.integers(32))
            assert block_checksums(g, counts)[b] != clean[b]


def test_partitioned_array_detect_or_defined():
    """DP-partitioned (variable-count) arrays pass the validators clean and
    keep the detect contract under corruption."""
    from repro.index.partition import choose_partition, encode_partitioned

    rng = np.random.default_rng(3)
    gaps = rng.integers(1, 9, 900).astype(np.uint64)
    gaps[rng.random(900) < 0.02] += 100_000
    vals = np.cumsum(gaps).astype(np.uint64)
    part = choose_partition(vals, block_size=64)
    arr = encode_partitioned(vals, part.bounds, format=part.format,
                             block_size=64, differential=True,
                             checksum=True)
    assert _detect(arr) is None
    for cls in ("bit_flip", "count_under", "width_deflate"):
        c = faultgen.corrupt(arr, cls, 0)
        if c is None:
            continue
        assert isinstance(_detect(c.arr), DecodeError), cls


# ---------------------------------------------------------------------------
# deadline-degraded query semantics
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def small_index():
    rng = np.random.default_rng(0)
    lists = {t: np.unique(rng.integers(0, 1 << 16, 300)) for t in range(4)}
    tfs = {t: 1 + (np.arange(len(v)) % 6) for t, v in lists.items()}
    return build_index(lists, tfs=tfs, n_docs=1 << 16, checksum=True)


def _expired_deadline():
    return Deadline(0.0, clock=lambda: 1.0, start=0.0)


class TestDeadlines:
    def test_deadline_expiry_is_monotonic(self):
        t = {"v": 0.0}
        d = Deadline(5.0, clock=lambda: t["v"])
        assert not d.expired() and d.remaining() == 5.0
        t["v"] = 6.0
        assert d.expired() and d.remaining() == 0.0
        t["v"] = 0.0  # clock regression cannot un-expire (`hit` latches)
        assert d.expired()

    def test_conjunctive_expired_returns_flagged_superset(self, small_index):
        exact = conjunctive(small_index, [0, 1, 2])
        st = QueryStats()
        out = conjunctive(small_index, [0, 1, 2], stats=st,
                          deadline=_expired_deadline())
        assert st.degraded and any(r.startswith("deadline:")
                                   for r in st.degraded_reasons)
        assert np.isin(exact, out).all()  # AND degrades to a superset

    def test_disjunctive_expired_returns_flagged_subset(self, small_index):
        exact = disjunctive(small_index, [0, 1, 2])
        st = QueryStats()
        out = disjunctive(small_index, [0, 1, 2], stats=st,
                          deadline=_expired_deadline())
        assert st.degraded
        assert np.isin(out, exact).all()  # OR degrades to a subset

    def test_topk_expired_flags_and_returns_defined(self, small_index):
        st = QueryStats()
        ids, scores = topk(small_index, [0, 1, 2, 3], 10, mode="maxscore",
                           stats=st, deadline=_expired_deadline())
        assert st.degraded
        assert ids.dtype == np.uint32 and scores.dtype == np.int32
        assert ids.shape == scores.shape

    def test_no_deadline_is_bit_exact_and_unflagged(self, small_index):
        st = QueryStats()
        a = topk(small_index, [0, 1, 2], 10, mode="maxscore", stats=st)
        b = topk(small_index, [0, 1, 2], 10, mode="or")
        assert not st.degraded
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# the hardened SearchEngine (launch/serve.py)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine_index():
    rng = np.random.default_rng(1)
    lists = {t: np.unique(rng.integers(0, 1 << 18, 400)) for t in range(8)}
    tfs = {t: 1 + (np.arange(len(v)) % 5) for t, v in lists.items()}
    return build_index(lists, tfs=tfs, n_docs=1 << 18, checksum=True)


class TestHardenedEngine:
    def _mk(self, index, **kw):
        from repro.launch.serve import SearchEngine

        return SearchEngine(index, **kw)

    def test_transient_fault_retried_to_exact_result(self, engine_index):
        def hook(attempt, terms, mode):
            if attempt == 0:
                raise ChecksumError("injected", format="vbyte", block=0)

        eng = self._mk(engine_index, fault_hook=hook, max_retries=2)
        st = QueryStats()
        out = eng.search([0, 1], "topk_maxscore", stats=st)
        ref = self._mk(engine_index).search([0, 1], "topk_maxscore")
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(a, b)
        assert st.retries == 1 and st.errors == 1 and not st.degraded

    def test_retries_exhausted_degrades_never_hangs(self, engine_index):
        def hook(attempt, terms, mode):
            raise ChecksumError("persistent")

        eng = self._mk(engine_index, fault_hook=hook, max_retries=2)
        st = QueryStats()
        out = eng.search([0, 1], "or", stats=st)
        assert out.size == 0
        assert st.degraded and "retries-exhausted" in st.degraded_reasons
        assert st.errors == 3 and st.retries == 2
        assert eng.serve_stats["degraded_responses"] == 1

    def test_term_coordinate_fault_quarantines_segment(self, engine_index):
        def hook(attempt, terms, mode):
            if 1 in terms:
                raise ChecksumError("bad segment", block=2, term=1)

        eng = self._mk(engine_index, fault_hook=hook)
        st = QueryStats()
        out = eng.search([0, 1], "or", stats=st)
        np.testing.assert_array_equal(
            out, self._mk(engine_index).search([0], "or"))
        assert 1 in eng.quarantined and st.degraded
        assert st.quarantined_blocks == 0  # charged at fault time, not twice
        st2 = QueryStats()
        eng.search([1], "or", stats=st2)  # later queries skip it up front
        assert st2.degraded and st2.quarantined_blocks > 0

    def test_startup_validation_quarantines_corrupt_stream(self, engine_index):
        terms = dict(engine_index.terms)
        bad = faultgen.corrupt(terms[2].arr, "bit_flip", 5)
        terms[2] = dataclasses.replace(terms[2], arr=bad.arr)
        index = dataclasses.replace(engine_index, terms=terms)
        eng = self._mk(index, validate=True)
        assert 2 in eng.quarantined
        assert eng.serve_stats["quarantined_blocks"] == terms[2].n_blocks
        st = QueryStats()
        out = eng.search([2, 3], "or", stats=st)
        np.testing.assert_array_equal(
            out, self._mk(engine_index).search([3], "or"))
        assert st.degraded

    def test_unsafe_bound_forces_exact_taat_fallback(self, engine_index):
        terms = dict(engine_index.terms)
        terms[3] = faultgen.corrupt_max_impact(terms[3], 7)
        index = dataclasses.replace(engine_index, terms=terms)
        eng = self._mk(index, validate=True, deep_validate=True)
        assert 3 in eng.bound_unsafe and 3 not in eng.quarantined
        st = QueryStats()
        out = eng.search([3, 4], "topk_maxscore", stats=st)
        # the fallback answers from the SAME (bound-corrupt) index — exact
        # because TAAT never consults max_impact — matching the clean index
        ref = self._mk(engine_index).search([3, 4], "topk")
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(a, b)
        assert st.bound_fallbacks == 1 and not st.degraded

    def test_dead_shard_partial_results_then_heal(self, engine_index):
        eng = self._mk(engine_index, n_shards=4)
        victim_terms = eng.term_order[slice(*eng.shards[1])]
        clean = eng.search(list(engine_index.terms), "or")
        eng.kill_shard(1)
        st = QueryStats()
        out = eng.search(list(engine_index.terms), "or", stats=st)
        assert st.degraded and any(r.startswith("dead-shard:")
                                   for r in st.degraded_reasons)
        assert np.isin(out, clean).all() and out.size < clean.size
        plan = eng.heal()
        assert len(plan) == 3 and not eng.dead_shards
        assert all(eng.shard_of[t] < 3 for t in victim_terms)
        st2 = QueryStats()
        np.testing.assert_array_equal(
            eng.search(list(engine_index.terms), "or", stats=st2), clean)
        assert not st2.degraded

    def test_engine_deadline_budget_flags_response(self, engine_index):
        t = {"v": 0.0}

        def clock():
            t["v"] += 0.3
            return t["v"]

        eng = self._mk(engine_index, deadline_s=0.1, clock=clock)
        st = QueryStats()
        eng.search([0, 1, 2], "or", stats=st)
        assert st.degraded
        assert eng.serve_stats["degraded_responses"] == 1

    def test_stats_merge_aggregates_per_query(self):
        agg, one = QueryStats(), QueryStats()
        one.count(3, decoded=2, skipped=1, ints=10)
        one.mark_degraded("deadline:test")
        one.retries = 2
        agg.merge(one)
        agg.merge(one)
        assert agg.blocks_decoded == 4 and agg.retries == 4
        assert agg.degraded and agg.degraded_reasons == ["deadline:test"]


# ---------------------------------------------------------------------------
# SearchEngine over a LiveIndex-merged segment (format="auto", DP-partitioned)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def merged_segment(tmp_path_factory):
    """An index produced by the ingestion path: stream docs into a
    LiveIndex, background-merge into a ``format="auto"`` segment, reload
    it from disk — the exact artifact serving sees after a merge."""
    import os

    from repro.index import LiveIndex
    from repro.index.ingest import load_segment

    d = str(tmp_path_factory.mktemp("live") / "ix")
    rng = np.random.default_rng(4)
    live = LiveIndex(d, n_docs=1 << 16, fsync=False)
    for doc in np.unique(rng.integers(0, 1 << 16, 500)):
        live.add(int(doc), {int(t): int(rng.integers(1, 5))
                            for t in rng.choice(8, rng.integers(1, 4),
                                                replace=False)})
    live.merge()
    seg = os.path.join(d, "segments", sorted(os.listdir(
        os.path.join(d, "segments")))[0])
    index, _tfs, _docs = load_segment(seg)
    live.close()
    return index


class TestEngineOnMergedSegment:
    def _mk(self, index, **kw):
        from repro.launch.serve import SearchEngine

        return SearchEngine(index, **kw)

    def test_startup_validation_passes_clean_merged_segment(self, merged_segment):
        assert merged_segment.format == "auto"
        # the DP partitioner assigned real per-term codecs round-tripped
        # through segment persistence
        fmts = {tp.arr.format for tp in merged_segment.terms.values()}
        assert fmts and fmts <= set(FORMATS)
        eng = self._mk(merged_segment, validate=True, deep_validate=True)
        assert not eng.quarantined and not eng.bound_unsafe
        st = QueryStats()
        out = eng.search([0, 1, 2], "topk_maxscore", stats=st)
        ref = topk(merged_segment, [0, 1, 2], 10, mode="or")
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(a, b)
        assert not st.degraded

    def test_startup_quarantine_on_corrupt_merged_term(self, merged_segment):
        terms = dict(merged_segment.terms)
        bad = faultgen.corrupt(terms[5].arr, "bit_flip", 11)
        terms[5] = dataclasses.replace(terms[5], arr=bad.arr)
        index = dataclasses.replace(merged_segment, terms=terms)
        eng = self._mk(index, validate=True)
        assert 5 in eng.quarantined
        st = QueryStats()
        out = eng.search([5, 6], "or", stats=st)
        np.testing.assert_array_equal(
            out, self._mk(merged_segment).search([6], "or"))
        assert st.degraded

    def test_heal_after_shard_loss_on_merged_segment(self, merged_segment):
        eng = self._mk(merged_segment, n_shards=3)
        all_terms = list(merged_segment.terms)
        clean = eng.search(all_terms, "or")
        eng.kill_shard(0)
        st = QueryStats()
        partial = eng.search(all_terms, "or", stats=st)
        assert st.degraded and partial.size < clean.size
        eng.heal()
        assert not eng.dead_shards
        np.testing.assert_array_equal(eng.search(all_terms, "or"), clean)
