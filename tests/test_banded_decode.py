"""Banded chunked-scatter decode cores: bit-exactness against the dense
cores, the gather oracle and the jnp decoders, across every edge the band
decomposition must preserve — count=0 blocks, uniform max-length blocks
(all-5-byte vbyte / all-4-byte streamvbyte), integers straddling chunk
boundaries, ragged tails, non-dividing chunk widths — plus the dispatch
plan axis (fused epilogues, differential on/off, jnp chunked grids) and
the chunk-width validation contract."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import CompressedIntArray
from repro.core.vbyte import masked as vmasked
from repro.core.vbyte import stream_masked as svb_masked
from repro.kernels.vbyte_decode import (dispatch, stream_vbyte_decode_blocked,
                                        vbyte_decode_blocked,
                                        vbyte_decode_blocked_ref)
from repro.kernels.vbyte_decode.banded import (normalize_chunk_width,
                                               place_bands, routing_cost,
                                               routing_reduction)
from repro.kernels.vbyte_decode.dispatch import DecodePlan
from repro.kernels.vbyte_decode.kernel import decode_tile
from repro.kernels.vbyte_decode.stream_kernel import stream_decode_tile

from conftest import make_valid_stream


def _tile_operands(vals, fmt, block_size, **enc):
    arr = CompressedIntArray.encode(vals, format=fmt, block_size=block_size,
                                    **enc)
    ops = arr.device_operands()
    counts2 = jnp.asarray(
        np.asarray(ops["counts"]).reshape(-1, 1).astype(np.int32))
    return arr, ops, counts2


# vbyte's unchunked route, the compaction core, is many small lane shifts:
# one compile runs it faster than op-by-op dispatch
_compact_tile = jax.jit(decode_tile, static_argnames=("block_size",))


def _assert_banded_equals_dense(vals, fmt, block_size, chunk_width, **enc):
    arr, ops, counts2 = _tile_operands(vals, fmt, block_size, **enc)
    if fmt == "vbyte":
        args = (jnp.asarray(ops["payload"]), counts2)
        dense, vd = _compact_tile(*args, block_size=block_size)
        band, vb = decode_tile(*args, block_size=block_size,
                               chunk_width=chunk_width)
    else:
        args = (jnp.asarray(ops["control"]), jnp.asarray(ops["data"]), counts2)
        dense, vd = stream_decode_tile(*args, block_size=block_size)
        band, vb = stream_decode_tile(*args, block_size=block_size,
                                      chunk_width=chunk_width)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(band))
    np.testing.assert_array_equal(np.asarray(vd), np.asarray(vb))
    # and the decoded prefix round-trips to the input values
    flat = np.asarray(band).reshape(-1)[: len(vals)].astype(np.uint32)
    np.testing.assert_array_equal(flat.astype(np.uint64),
                                  vals.astype(np.uint64) & 0xFFFFFFFF)
    return arr


# ---------------------------------------------------------------------------
# core parity sweeps
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", ["vbyte", "streamvbyte"])
@pytest.mark.parametrize("chunk_width", [8, 16, 24, 32, 64, 128])
def test_banded_equals_dense_mixed_lengths(rng, fmt, chunk_width):
    vals = make_valid_stream(rng, 1000)
    _assert_banded_equals_dense(vals, fmt, 128, chunk_width)


@pytest.mark.parametrize("fmt", ["vbyte", "streamvbyte"])
@pytest.mark.parametrize("block_size,chunk_width", [(8, 8), (32, 16), (64, 24)])
def test_banded_small_blocks(rng, fmt, block_size, chunk_width):
    vals = make_valid_stream(rng, 333)
    _assert_banded_equals_dense(vals, fmt, block_size, chunk_width)


@pytest.mark.parametrize("fmt", ["vbyte", "streamvbyte"])
def test_banded_tight_strides(rng, fmt):
    # stride_multiple=8 gives non-128-aligned payload strides that the
    # chunk grid must pad internally
    vals = make_valid_stream(rng, 300)
    _assert_banded_equals_dense(vals, fmt, 64, 48, stride_multiple=8)


def test_banded_all_five_byte_blocks():
    # every integer 2^32-1: vbyte blocks are uniformly 5 bytes/int, so
    # every chunk boundary splits an integer — the straddle-combine path
    # carries (almost) every output
    vals = np.full(257, 2**32 - 1, np.uint64)
    for W in (8, 32, 64):
        _assert_banded_equals_dense(vals, "vbyte", 128, W)


def test_banded_all_four_byte_blocks():
    # uniform 4-byte stream blocks: 4W data bytes per W-integer chunk —
    # the tight end of the ends-band bound
    vals = np.full(257, 2**32 - 1, np.uint64)
    for W in (8, 32, 64):
        _assert_banded_equals_dense(vals, "streamvbyte", 128, W)


def test_banded_all_one_byte_blocks():
    # all-zero values: 1 byte/int, maximal terminator density — chunk
    # bases grow fastest and the last chunks hold only padding
    vals = np.zeros(300, np.uint64)
    for fmt in ("vbyte", "streamvbyte"):
        _assert_banded_equals_dense(vals, fmt, 128, 32)


def test_banded_straddle_forced(rng):
    # W=8 with 2-5 byte integers: nearly every chunk boundary cuts an
    # integer in half; both chunks' partial sums must recombine exactly
    vals = make_valid_stream(rng, 400, max_bits=32)
    vals |= 1 << 14  # ≥3 bytes in vbyte, ≥2 data bytes in streamvbyte
    for fmt in ("vbyte", "streamvbyte"):
        _assert_banded_equals_dense(vals, fmt, 128, 8)


@pytest.mark.parametrize("fmt", ["vbyte", "streamvbyte"])
@pytest.mark.parametrize("n", [1, 7, 129, 1000])
def test_banded_ragged_tails(rng, fmt, n):
    vals = make_valid_stream(rng, n)
    _assert_banded_equals_dense(vals, fmt, 128, 32)


def test_banded_count_zero_blocks(rng):
    # append all-padding blocks (count 0, zero payload) to real operands —
    # the shape the sharded path's block padding produces
    vals = make_valid_stream(rng, 260)
    for fmt in ("vbyte", "streamvbyte"):
        arr, ops, _ = _tile_operands(vals, fmt, 128)
        padded = {
            k: jnp.asarray(np.concatenate(
                [np.asarray(v), np.zeros((2,) + np.asarray(v).shape[1:],
                                         np.asarray(v).dtype)]))
            for k, v in ops.items()
        }
        kw = dict(block_size=128, differential=False)
        if fmt == "vbyte":
            dense = vbyte_decode_blocked(**padded, **kw)
            band = vbyte_decode_blocked(**padded, chunk_width=32, **kw)
        else:
            dense = stream_vbyte_decode_blocked(**padded, **kw)
            band = stream_vbyte_decode_blocked(**padded, chunk_width=32, **kw)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(band))
        assert not np.asarray(band)[-2:].any()  # count-0 rows decode to 0


# ---------------------------------------------------------------------------
# kernel wrappers, oracles, differential
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", ["vbyte", "streamvbyte"])
@pytest.mark.parametrize("differential", [False, True])
def test_banded_kernel_vs_oracles(rng, fmt, differential):
    if differential:
        vals = np.sort(rng.integers(0, 2**31, size=777)).astype(np.uint64)
    else:
        vals = make_valid_stream(rng, 777)
    arr = CompressedIntArray.encode(vals, format=fmt,
                                    differential=differential)
    ops = arr.device_operands()
    kw = dict(block_size=128, differential=differential)
    if fmt == "vbyte":
        band = vbyte_decode_blocked(**ops, chunk_width=64, **kw)
        ref = vbyte_decode_blocked_ref(**ops, **kw)
        msk = vmasked.decode_blocked(**ops, **kw)
    else:
        band = stream_vbyte_decode_blocked(**ops, chunk_width=64, **kw)
        ref = svb_masked.decode_blocked(**ops, **kw)
        msk = svb_masked.decode_blocked(**ops, chunk_width=64, **kw)
    np.testing.assert_array_equal(np.asarray(band), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(band), np.asarray(msk))


@pytest.mark.parametrize("fmt", ["vbyte", "streamvbyte"])
def test_jnp_chunked_grid_equals_dense(rng, fmt):
    # the chunked prefix decomposition of the vectorized jnp decoders is
    # value-identical to the plain cumsum by construction
    vals = make_valid_stream(rng, 500)
    arr = CompressedIntArray.encode(vals, format=fmt)
    ops = arr.device_operands()
    dec = vmasked.decode_blocked if fmt == "vbyte" else svb_masked.decode_blocked
    kw = dict(block_size=128, differential=False)
    a = dec(**ops, **kw)
    for W in (24, 32, 128):
        b = dec(**ops, chunk_width=W, **kw)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# dispatch plan axis + fused epilogues
# ---------------------------------------------------------------------------
def test_plan_chunk_axis_label_and_validation():
    assert DecodePlan("pallas", True, 8, 64).label == "pallas_fused_bt8_w64"
    assert DecodePlan("jnp", False, chunk=32).label == "jnp_unfused_w32"
    assert DecodePlan("jnp", True).label == "jnp_fused"
    with pytest.raises(ValueError):
        DecodePlan("pallas", True, 8, 12)  # not a multiple of 8
    with pytest.raises(ValueError):
        DecodePlan("pallas", True, 8, -8)
    with pytest.raises(ValueError):
        normalize_chunk_width(256, 128)  # band wider than the output
    assert normalize_chunk_width(64, 128) == 64


def test_default_chunk_clamped_to_block_size(rng):
    # heuristic chunk widths (DEFAULT_CHUNK, plan="banded") must shrink to
    # the workload's block size instead of tripping the band-width check
    assert dispatch._clamp_chunk(64, 32) == 32
    assert dispatch._clamp_chunk(64, 24) == 24
    assert dispatch._clamp_chunk(32, 128) == 32
    assert dispatch._clamp_chunk(None, 8) is None
    assert dispatch._clamp_chunk(64, 4) is None
    for fmt in ("vbyte", "streamvbyte"):
        plan = dispatch.resolve_plan("banded", format=fmt,
                                     epilogue="stream", block_size=8)
        assert plan.chunk is None or plan.chunk <= 8
        vals = make_valid_stream(rng, 100)
        arr = CompressedIntArray.encode(vals, format=fmt, block_size=8)
        np.testing.assert_array_equal(arr.decode(plan="banded"),
                                      arr.decode(plan="dense"))


def test_plan_strings_banded_dense(rng):
    vals = np.sort(rng.integers(0, 10000, size=300)).astype(np.uint64)
    for fmt in ("vbyte", "streamvbyte"):
        arr = CompressedIntArray.encode(vals, format=fmt, differential=True)
        a = arr.decode(plan="banded")
        b = arr.decode(plan="dense")
        c = arr.decode(plan="jnp")
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_plan_resolution_with_chunk_cache_entry(tmp_path, monkeypatch):
    import json

    cache = {"cpu/vbyte/stream/bs128": {
        "schema": dispatch.CACHE_SCHEMA,  # untagged entries are migrated away
        "plan": {"path": "jnp", "fused": True, "block_tile": 8, "chunk": 32}}}
    p = tmp_path / "autotune.json"
    p.write_text(json.dumps(cache))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(p))
    dispatch.load_cache(str(p), reload=True)
    try:
        plan = dispatch.resolve_plan("auto", format="vbyte",
                                     epilogue="stream", block_size=128)
        if jax.default_backend() == "cpu":
            assert plan.chunk == 32
        # legacy entries without "chunk" resolve to dense
        plan2 = dispatch.resolve_plan(
            "auto", format="vbyte", epilogue="dot_score", block_size=128)
        assert plan2.chunk is None or isinstance(plan2.chunk, int)
    finally:
        dispatch.load_cache(reload=True)


@pytest.mark.parametrize("fmt", ["vbyte", "streamvbyte"])
@pytest.mark.parametrize("epilogue", ["bag_sum", "dot_score",
                                      "adjacency_rebase"])
def test_banded_fused_epilogues_parity(rng, fmt, epilogue):
    vals = np.sort(rng.integers(0, 2048, size=300)).astype(np.uint64)
    arr = CompressedIntArray.encode(vals, format=fmt, differential=True)
    ops = arr.device_operands()
    table = jnp.asarray(rng.standard_normal((2048, 8)).astype(np.float32))
    extras = {
        "bag_sum": {"table": table},
        "dot_score": {"table": table, "query": jnp.asarray(
            rng.standard_normal((1, 8)).astype(np.float32))},
        "adjacency_rebase": {"edge_base": jnp.asarray(
            rng.integers(0, 2048, (arr.n_blocks, 128)).astype(np.int32))},
    }[epilogue]
    outs = []
    for plan in (DecodePlan("pallas", True, 8, chunk=32),
                 DecodePlan("jnp", True, chunk=32),
                 "unfused"):
        o = dispatch.decode(ops, format=fmt, block_size=128,
                            differential=True, epilogue=epilogue,
                            epilogue_operands=extras, plan=plan)
        outs.append([np.asarray(x) for x in
                     (o if isinstance(o, tuple) else (o,))])
    for other in outs[1:]:
        for x, y in zip(outs[0], other):
            np.testing.assert_array_equal(x, y)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >1 device (CI sharded job forces 8)")
def test_banded_sharded_parity(rng):
    vals = np.sort(rng.integers(0, 2**20, size=1200)).astype(np.uint64)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    for fmt in ("vbyte", "streamvbyte"):
        arr = CompressedIntArray.encode(vals, format=fmt, differential=True)
        sh = arr.shard(mesh)
        single = dispatch.decode(arr, plan=DecodePlan("jnp", True, chunk=32))
        sharded = dispatch.decode(sh, plan=DecodePlan("jnp", True, chunk=32))
        np.testing.assert_array_equal(
            np.asarray(single), np.asarray(sharded)[: arr.n_blocks])


# ---------------------------------------------------------------------------
# banded primitives + cost model
# ---------------------------------------------------------------------------
def test_place_bands_overlap_and_clip():
    bands = [jnp.asarray([[1, 2, 0]], jnp.int32),
             jnp.asarray([[3, 4, 5]], jnp.int32)]
    off = [jnp.asarray([[1]], jnp.int32), jnp.asarray([[2]], jnp.int32)]
    out = np.asarray(place_bands(bands, off, 6))
    # band 0 -> cols 1..3, band 1 -> cols 2..4 (overlap at 2..3 adds)
    np.testing.assert_array_equal(out, [[0, 1, 5, 4, 5, 0]])
    # offsets ≥ out_width push the whole band off the end
    far = [jnp.asarray([[6]], jnp.int32), jnp.asarray([[7]], jnp.int32)]
    out2 = np.asarray(place_bands(bands, far, 6))
    np.testing.assert_array_equal(out2, np.zeros((1, 6), np.int32))


def test_routing_cost_model_reduction():
    # the headline acceptance numbers: ≥4x modeled routing-MAC reduction
    # at the default shapes with the per-format default chunk widths
    assert routing_reduction("vbyte", S=640, B=128, W=64) >= 4.0
    assert routing_reduction("streamvbyte", S=512, B=128, W=32) >= 4.0
    d = routing_cost("vbyte", S=640, B=128, W=None)
    b = routing_cost("vbyte", S=640, B=128, W=64)
    assert b["vmem_total"] < d["vmem_total"] / 2  # the VMEM shrink is real
    assert b["vpu_total"] <= d["vpu_total"]
    with pytest.raises(ValueError):
        routing_cost("nope", S=640, B=128, W=64)
