"""Masked-VByte compaction routing (``kernel.decode_tile``), vbyte's one
route: bit-exact against the gather oracle and the jnp decoder at the
strides and block sizes the index uses and at the encoder's own strides, on
the edge blocks (count 0, all 1-byte, all 5-byte, ragged tails), in
differential mode with bases that wrap past 2^32, and fused into the
epilogues; the compaction and prefix-count helpers alone on random inputs
that meet their contracts; and a jaxpr check that the route issues no
matmul."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import CompressedIntArray
from repro.core.vbyte import masked as vmasked
from repro.kernels.vbyte_decode import (dispatch, vbyte_decode_blocked,
                                        vbyte_decode_blocked_ref)
from repro.kernels.vbyte_decode.dispatch import DecodePlan
from repro.kernels.vbyte_decode.kernel import (_compact_left,
                                               _row_prefix_count, decode_tile,
                                               prefix_sum_tile)

from conftest import make_valid_stream


def _grid(vals, block_size, stride=None, stride_multiple=8):
    """Encoded gap grid ``(payload uint8 [n, S], counts int32 [n])``, the
    payload zero-padded on the right to ``stride`` bytes when given."""
    ops = CompressedIntArray.encode(
        vals, block_size=block_size, differential=False,
        stride_multiple=stride_multiple).device_operands()
    payload = np.asarray(ops["payload"])
    if stride is not None:
        assert payload.shape[1] <= stride, (payload.shape, stride)
        payload = np.pad(payload, ((0, 0), (0, stride - payload.shape[1])))
    return payload, np.asarray(ops["counts"]).astype(np.int32)


_tile = jax.jit(decode_tile, static_argnames=("block_size",))


def _assert_matches_oracles(payload, counts, bases=None, *, block_size,
                            differential=False, host=False):
    """The Pallas kernel (interpret mode, 8 blocks a grid step) equals the
    gather oracle and the jnp decoder; with ``host=True`` so does the tile
    core run over the whole grid at once, as host-level code."""
    n = payload.shape[0]
    bases = np.zeros(n, np.uint32) if bases is None else bases
    kw = dict(block_size=block_size, differential=differential)
    args = (jnp.asarray(payload), jnp.asarray(counts), jnp.asarray(bases))
    ref = np.asarray(vbyte_decode_blocked_ref(*args, **kw))
    np.testing.assert_array_equal(
        np.asarray(vmasked.decode_blocked(*args, **kw)), ref)
    got = vbyte_decode_blocked(*args, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(got), ref)
    if host:
        out, valid = _tile(args[0], args[1][:, None], block_size=block_size)
        if differential:
            out = prefix_sum_tile(out, valid, jax.lax.bitcast_convert_type(
                args[2], jnp.int32)[:, None])
        np.testing.assert_array_equal(np.asarray(out).view(np.uint32), ref)
    return ref


# ---------------------------------------------------------------------------
# parity with the oracles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("block_size", [8, 32, 64, 128])
@pytest.mark.parametrize("stride", [128, 256, 384, 640])
def test_compact_matches_oracles(rng, stride, block_size):
    # the widest integers that still fit the stride at this block size
    max_bytes = min(5, stride // block_size)
    vals = make_valid_stream(rng, 5 * block_size + 3,
                             max_bits=min(32, 7 * max_bytes))
    payload, counts = _grid(vals, block_size, stride)
    ref = _assert_matches_oracles(payload, counts, block_size=block_size)
    flat = ref.reshape(-1)[: len(vals)]
    np.testing.assert_array_equal(flat, vals.astype(np.uint32))


@pytest.mark.parametrize("block_size", [8, 64, 128])
def test_compact_tight_unaligned_stride(rng, block_size):
    # stride_multiple=1: the stride is the largest block's byte count, not a
    # multiple of 128 (nor of 8)
    vals = make_valid_stream(rng, 3 * block_size + 5)
    payload, counts = _grid(vals, block_size, stride_multiple=1)
    assert payload.shape[1] % 8, payload.shape
    _assert_matches_oracles(payload, counts, block_size=block_size, host=True)


@pytest.mark.parametrize("value,nbytes", [(0, 1), (127, 1), (2**32 - 1, 5)])
def test_compact_uniform_length_blocks(value, nbytes):
    # all-1-byte blocks: a terminator on every lane, nothing moves; all-5-byte
    # blocks: every terminator moves by four lanes per earlier integer
    vals = np.full(3 * 128 + 1, value, np.uint64)
    payload, counts = _grid(vals, 128, 128 * nbytes)
    ref = _assert_matches_oracles(payload, counts, block_size=128)
    assert (ref.reshape(-1)[: len(vals)] == value).all()


@pytest.mark.parametrize("n", [1, 7, 129, 1000])
def test_compact_ragged_tails(rng, n):
    vals = make_valid_stream(rng, n)
    payload, counts = _grid(vals, 128, 640)
    _assert_matches_oracles(payload, counts, block_size=128)


def test_compact_count_zero_blocks(rng):
    # all-padding rows (count 0, zero bytes: every lane a dead terminator)
    # between and after real ones, as the sharded path pads
    payload, counts = _grid(make_valid_stream(rng, 300), 128, 640)
    zeros = np.zeros((2, payload.shape[1]), np.uint8)
    payload = np.concatenate([payload[:1], zeros, payload[1:], zeros])
    counts = np.concatenate([counts[:1], [0, 0], counts[1:], [0, 0]])
    counts = counts.astype(np.int32)
    ref = _assert_matches_oracles(payload, counts, block_size=128, host=True)
    assert not ref[[1, 2, -2, -1]].any()


def test_compact_short_counts_mask_real_integers(rng):
    # a count below the block's integers: real terminators past the count
    # land on slots ≥ count, which must read 0
    payload, counts = _grid(make_valid_stream(rng, 256), 128, 640)
    counts[0] = 5
    ref = _assert_matches_oracles(payload, counts, block_size=128)
    assert not ref[0, 5:].any() and ref[0, :5].any()


@pytest.mark.parametrize("stride", [256, 640])
def test_compact_differential_bases_wrap(rng, stride):
    # per-block carry-in bases near 2^32 plus gaps of up to 32 bits: the
    # prefix sums wrap mod 2^32 on every row
    vals = make_valid_stream(rng, 4 * 128 + 9,
                             max_bits=32 if stride == 640 else 14)
    payload, counts = _grid(vals, 128, stride)
    bases = (np.uint32(2**32 - 1000)
             - rng.integers(0, 500, len(counts)).astype(np.uint32))
    ref = _assert_matches_oracles(payload, counts, bases, block_size=128,
                                  differential=True, host=True)
    gaps = np.zeros(len(counts) * 128, np.uint64)
    gaps[: len(vals)] = vals
    want = (bases[:, None].astype(np.uint64)
            + np.cumsum(gaps.reshape(-1, 128), axis=1)) % 2**32
    want[np.arange(128)[None, :] >= counts[:, None]] = 0
    np.testing.assert_array_equal(ref, want.astype(np.uint32))


# lists as the encoder lays them out (its own strides), each case one
# (values, block size, encoder options): the block sizes, tight strides,
# uniform-length blocks, ragged tails and both differential modes
ENCODED = {
    "block_size_8": lambda rng: (make_valid_stream(rng, 333), 8, {}),
    "block_size_32": lambda rng: (make_valid_stream(rng, 333), 32, {}),
    "block_size_64": lambda rng: (make_valid_stream(rng, 333), 64, {}),
    "stride_multiple_8": lambda rng: (make_valid_stream(rng, 300), 64,
                                      {"stride_multiple": 8}),
    "all_five_byte": lambda rng: (np.full(257, 2**32 - 1, np.uint64), 128,
                                  {}),
    "all_one_byte": lambda rng: (np.zeros(300, np.uint64), 128, {}),
    "three_bytes_and_up": lambda rng: (
        make_valid_stream(rng, 400) | np.uint64(1 << 14), 128, {}),
    **{f"ragged_{n}": (lambda rng, n=n: (make_valid_stream(rng, n), 128, {}))
       for n in (1, 7, 129, 1000)},
    "count_zero_blocks": lambda rng: (make_valid_stream(rng, 260), 128, {}),
    "gaps": lambda rng: (make_valid_stream(rng, 777), 128, {}),
    "differential": lambda rng: (
        np.sort(rng.integers(0, 2**31, size=777)).astype(np.uint64), 128,
        {"differential": True}),
}


@pytest.mark.parametrize("case", list(ENCODED))
def test_compact_matches_oracles_at_encoder_strides(rng, case):
    vals, block_size, enc = ENCODED[case](rng)
    differential = enc.pop("differential", False)
    ops = CompressedIntArray.encode(vals, block_size=block_size,
                                    differential=differential,
                                    **enc).device_operands()
    payload, counts, bases = (np.asarray(ops[k])
                              for k in ("payload", "counts", "bases"))
    counts = counts.astype(np.int32)
    if case == "count_zero_blocks":  # all-padding rows, as sharding pads
        payload = np.pad(payload, ((0, 2), (0, 0)))
        counts = np.pad(counts, (0, 2))
        bases = np.pad(bases, (0, 2))
    ref = _assert_matches_oracles(payload, counts, bases,
                                  block_size=block_size,
                                  differential=differential, host=True)
    live = np.arange(block_size)[None, :] < counts[:, None]
    np.testing.assert_array_equal(ref[live], vals.astype(np.uint32))


@pytest.mark.parametrize("epilogue", ["bag_sum", "dot_score",
                                      "adjacency_rebase"])
def test_compact_fused_epilogues_parity(rng, epilogue):
    # the fused Pallas kernels on the compaction core equal the fused jnp
    # program and the unfused reference, bit for bit
    vals = np.sort(rng.integers(0, 2048, size=300)).astype(np.uint64)
    arr = CompressedIntArray.encode(vals, differential=True)
    table = jnp.asarray(rng.standard_normal((2048, 8)).astype(np.float32))
    extras = {
        "bag_sum": {"table": table},
        "dot_score": {"table": table, "query": jnp.asarray(
            rng.standard_normal((1, 8)).astype(np.float32))},
        "adjacency_rebase": {"edge_base": jnp.asarray(
            rng.integers(0, 2048, (arr.n_blocks, 128)).astype(np.int32))},
    }[epilogue]
    outs = [jax.tree_util.tree_leaves(dispatch.decode(
        arr, epilogue=epilogue, epilogue_operands=extras, plan=plan))
        for plan in (DecodePlan("pallas", True, 8), DecodePlan("jnp", True),
                     "unfused")]
    for other in outs[1:]:
        for x, y in zip(outs[0], other):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# the helpers alone
# ---------------------------------------------------------------------------
# one stride per bit length 1..10 and both ends of it, so every number of
# radix-16 rounds and every partial last digit is taken
HELPER_STRIDES = sorted({s for b in range(1, 11)
                         for s in (2**(b - 1) + 1, 2**b)})


@pytest.mark.parametrize("S", HELPER_STRIDES)
def test_compact_left_lands_every_live_value(S):
    rng = np.random.default_rng(S)
    rows = 24
    vals = np.zeros((rows, S), np.int64)
    shift = np.zeros((rows, S), np.int64)
    want = np.zeros((rows, S), np.int64)
    for r in range(rows):
        n_live = int(rng.integers(0, S + 1)) if r else S
        targets = np.sort(rng.choice(S, n_live, replace=False))
        if r % 3 == 0:  # packed targets: the shifts reach the stride
            targets = np.arange(n_live)
        room = S - 1 - (targets[-1] if n_live else 0)
        d = np.sort(rng.integers(0, room + 1, n_live))  # non-decreasing
        src = targets + d  # strictly increasing, < S
        v = rng.integers(-2**31, 2**31, n_live)
        vals[r, src] = v
        shift[r, src] = d
        want[r, targets] = v
    got = jax.jit(_compact_left)(jnp.asarray(vals, jnp.int32),
                                 jnp.asarray(shift, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))


@pytest.mark.parametrize("S", HELPER_STRIDES)
def test_row_prefix_count_is_inclusive_cumsum(S):
    rng = np.random.default_rng(S)
    flags = rng.integers(0, 2, (8, S)).astype(np.int32)
    got = jax.jit(_row_prefix_count)(jnp.asarray(flags))
    np.testing.assert_array_equal(np.asarray(got), np.cumsum(flags, axis=1))


# ---------------------------------------------------------------------------
# no matmul in the route
# ---------------------------------------------------------------------------
def test_compact_route_issues_no_matmul(rng):
    payload, counts = _grid(make_valid_stream(rng, 300), 128, 640)
    p, c = jnp.asarray(payload), jnp.asarray(counts)
    tile = jax.make_jaxpr(lambda p, c: decode_tile(
        p, c[:, None], block_size=128))(p, c)
    assert "dot_general" not in str(tile)
    # the only matmuls left in the vbyte stream kernel are those of the
    # differential epilogue
    out = jnp.zeros((8, 128), jnp.int32)
    epi = str(jax.make_jaxpr(prefix_sum_tile)(
        out, out > 0, jnp.zeros((8, 1), jnp.int32)))
    kern = str(jax.make_jaxpr(lambda p, c, b: vbyte_decode_blocked(
        p, c, b, block_size=128, differential=True, interpret=True))(p, c, jnp.zeros(len(counts), jnp.uint32)))
    assert "vbyte_decode_compact" in kern
    assert kern.count("dot_general") == epi.count("dot_general") > 0
