"""Pallas TPU kernel: blocked Masked-VByte decode with fused differential sum.

TPU-native realization of the paper's decoder (docs/kernels.md). Per grid step a
(T, S)-byte VMEM tile (T blocks × S payload bytes — 8×640 = 5120 bytes,
~427× the paper's 12-byte unit, amortizing per-step overhead the way the
paper's 48-byte mask pipeline amortizes pmovmskb latency) is decoded entirely
branch-free:

  * continuation bits via one vectorized compare (pmovmskb analogue),
  * within-integer positions via the ≤5-byte closed form
    (replaces the 170 pshufb control masks),
  * each integer assembled at its terminator byte from the four bytes
    before it (static lane shifts),
  * byte→integer routing by **compaction** on the VPU: every terminator
    moves left by its count of earlier continuation bytes, one round of
    static lane shifts per radix-16 digit of that count (replaces the
    2^12-entry lookup table and the pshufb shuffle; docs/kernels.md
    §Compaction routing),
  * fused differential prefix sum via triangular matmul (the paper's
    pslldq/paddd doubling tree).

The routing is integer arithmetic only, exact mod 2^32 with no matmul. The
differential epilogue keeps 32-bit exactness on an f32 MXU by splitting every
32-bit word into 16-bit halves: per-output sums stay < 2^24 (f32-exact at
``precision=HIGHEST``) and are recombined with wrap-around int32 adds
(≡ mod 2^32, i.e. uint32).

All tensors live in VMEM; block dims are multiples of (8, 128) lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .banded import exact_dot, kernel_name


def _shift_right(x: jax.Array, k: int) -> jax.Array:
    """x[..., i-k] with zero fill — static slices only (Mosaic-safe)."""
    t, s = x.shape
    return jnp.concatenate([jnp.zeros((t, k), x.dtype), x[:, : s - k]], axis=1)


def _shift_left(x: jax.Array, k: int) -> jax.Array:
    """x[..., i+k] with zero fill — static slices only (Mosaic-safe)."""
    t, s = x.shape
    return jnp.concatenate([x[:, k:], jnp.zeros((t, k), x.dtype)], axis=1)


# Lane shifts on an 8-row tile are bound by latency, not throughput, so the
# log-step scans below take radix-16 digits: ⌈log₁₆ S⌉ rounds of up to 15
# independent shifts instead of ⌈log₂ S⌉ dependent ones (docs/kernels.md
# §Compaction routing).
_DIGIT_BITS = 4
_RADIX = 1 << _DIGIT_BITS


def _sum_tree(terms: list) -> jax.Array:
    """Sum of equal-shape int32 arrays as a balanced tree (exact, wraps)."""
    while len(terms) > 1:
        terms = ([a + b for a, b in zip(terms[::2], terms[1::2])]
                 + terms[len(terms) - len(terms) % 2:])
    return terms[0]


def _row_prefix_count(flags: jax.Array) -> jax.Array:
    """Inclusive row prefix sum of small int32 values, exact in int32.

    Hillis–Steele in radix 16: after the round of step k every lane holds
    the sum of the 16·k lanes ending at it, as the sum of 16 windows of k.
    """
    S = flags.shape[1]
    k = 1
    while k < S:
        flags = _sum_tree([flags] + [_shift_right(flags, a * k)
                                     for a in range(1, _RADIX) if a * k < S])
        k *= _RADIX
    return flags


def _terminator_values(contrib: jax.Array, c: tuple) -> jax.Array:
    """Each integer's value at its terminator byte, exact mod 2³².

    ``contrib[t, i]`` is byte i's payload bits already shifted to its
    within-integer position; ``c = (c1, c2, c3, c4)`` the continuation
    flags shifted right by 1…4 lanes (``cj[t, i] = cont[t, i-j]``). An
    integer has at most 5 bytes, so its value is its terminator's
    contribution plus those of the up-to-4 bytes before it, each taken
    while the bytes between continue:
    ``v_i = x_i + c1_i·(x_{i-1} + c2_i·(x_{i-2} + c3_i·(x_{i-3} + c4_i·x_{i-4})))``
    with ``x = contrib``. Lanes that are not terminators hold partial sums,
    which the caller masks.
    """
    val = _shift_right(contrib, 4) * c[3]
    for j in (3, 2, 1):
        val = (_shift_right(contrib, j) + val) * c[j - 1]
    return contrib + val


def _move_by_digit(x: jax.Array, digit: jax.Array, k: int) -> jax.Array:
    """Each ``x[t, i]`` moved left by ``digit[t, i]·2^k`` lanes, the moved
    pieces summed (one round of :func:`_compact_left`)."""
    parts = [jnp.where(digit == 0, x, 0)]
    parts += [_shift_left(jnp.where(digit == a, x, 0), a << k)
              for a in range(1, _RADIX) if a << k < x.shape[1]]
    return _sum_tree(parts)


def _compact_left(vals: jax.Array, shift: jax.Array) -> jax.Array:
    """Move ``vals[t, i]`` to lane ``i - shift[t, i]`` by static lane shifts,
    one round per radix-16 digit of the shift, least significant first:
    the round of the digit at bit k moves each element left by
    ``digit·2^k`` lanes.

    Contract: dead lanes carry ``vals = shift = 0``; over the live lanes of
    a row ``shift`` is non-decreasing and the targets ``i - shift`` are
    strictly increasing and ≥ 0. Then after every round the live elements
    sit on distinct lanes (docs/kernels.md §Compaction routing), so the
    moved pieces combine by an int32 add and every live value lands exactly.
    """
    n_bits = max(1, (vals.shape[1] - 1).bit_length())
    for k in range(0, n_bits, _DIGIT_BITS):
        digit = (shift >> k) & (_RADIX - 1)
        vals = _move_by_digit(vals, digit, k)
        if k + _DIGIT_BITS < n_bits:  # the last round's shifts go unread
            shift = _move_by_digit(shift, digit, k)
    return vals


def _row_cumsum_exact_u32(x: jax.Array, incl_tri: jax.Array) -> jax.Array:
    """Inclusive row cumsum of int32 values, exact mod 2^32 via 16-bit split."""
    lo = exact_dot(x & 0xFFFF, incl_tri)
    hi = exact_dot((x >> 16) & 0xFFFF, incl_tri)
    return lo + (hi << 16)


def decode_tile(payload: jax.Array, counts: jax.Array, *,
                block_size: int) -> tuple[jax.Array, jax.Array]:
    """Decode one VMEM tile of Masked-VByte bytes — the shared decode-tile core.

    ``payload`` is the raw ``uint8 [T, S]`` tile, ``counts`` the ``int32
    [T, 1]`` valid-integer counts. Returns ``(out, valid)``: ``out`` int32
    ``[T, B]`` (bitcast of uint32, masked rows zeroed) and ``valid`` bool
    ``[T, B]``. Pure jnp/lax — callable both from a Pallas kernel body and
    from host-level code; every fused epilogue consumes this contract.

    Routing is compaction: integers are assembled at their terminator
    bytes and compacted left on the VPU in ⌈log₁₆ S⌉ rounds of static lane
    shifts — integer arithmetic, no matmul.
    """
    T, S = payload.shape
    B = block_size

    b = payload.astype(jnp.int32)  # [T, S] bytes
    cont = b >> 7
    end = 1 - cont

    # within-integer byte position (≤ 4): closed form over preceding cont
    # flags — static shifts over the full row
    c1 = _shift_right(cont, 1)
    c2 = _shift_right(cont, 2)
    c3 = _shift_right(cont, 3)
    c4 = _shift_right(cont, 4)
    pos = c1 * (1 + c2 * (1 + c3 * (1 + c4)))

    contrib = (b & 0x7F) << (7 * pos)  # int32, wraps ≡ uint32

    # compaction routing: the integer ending at terminator i goes to slot
    # #terminators before i, i.e. i moves left by its count of earlier
    # continuation bytes; other lanes are dead (value, shift 0). Terminators
    # past the block's count (padding zeros) need no mask: they land on
    # slots ≥ count, which the valid mask below zeroes.
    term = end == 1
    val = jnp.where(term, _terminator_values(contrib, (c1, c2, c3, c4)), 0)
    shift = jnp.where(term, _row_prefix_count(cont) - cont, 0)
    out = _compact_left(val, shift)
    if S < B:
        out = jnp.concatenate([out, jnp.zeros((T, B - S), out.dtype)], axis=1)
    out = out[:, :B]

    jrow = lax.broadcasted_iota(jnp.int32, (T, B), 1)
    valid = jrow < counts
    out = jnp.where(valid, out, 0)
    return out, valid


def prefix_sum_tile(out: jax.Array, valid: jax.Array, bases: jax.Array) -> jax.Array:
    """Fused differential epilogue: inclusive row cumsum (mod 2^32) + bases.

    ``out`` int32 [T, B] gap values, ``bases`` int32 [T, 1] carry-in
    (bitcast of uint32). Shared by every format kernel.
    """
    B = out.shape[-1]
    kk = lax.broadcasted_iota(jnp.int32, (B, B), 0)
    ll = lax.broadcasted_iota(jnp.int32, (B, B), 1)
    incl_tri = (kk <= ll).astype(jnp.float32)
    out = _row_cumsum_exact_u32(out, incl_tri) + bases
    return jnp.where(valid, out, 0)


def _decode_tile_kernel(payload_ref, counts_ref, bases_ref, out_ref, *,
                        block_size: int, differential: bool):
    out, valid = decode_tile(payload_ref[...], counts_ref[...],
                             block_size=block_size)
    if differential:
        out = prefix_sum_tile(out, valid, bases_ref[...])
    out_ref[...] = out


def decode_blocked_pallas(
    payload: jax.Array,  # uint8 [n_blocks, stride]
    counts: jax.Array,  # int32 [n_blocks, 1]
    bases: jax.Array,  # int32 [n_blocks, 1] (bitcast of uint32)
    *,
    block_size: int,
    differential: bool,
    block_tile: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call wrapper; see ops.vbyte_decode_blocked for the public API."""
    nb, stride = payload.shape
    if nb % block_tile:
        raise ValueError(f"n_blocks={nb} must be a multiple of block_tile={block_tile}")
    grid = (nb // block_tile,)
    kernel = functools.partial(
        _decode_tile_kernel, block_size=block_size, differential=differential,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_tile, stride), lambda g: (g, 0)),
            pl.BlockSpec((block_tile, 1), lambda g: (g, 0)),
            pl.BlockSpec((block_tile, 1), lambda g: (g, 0)),
        ],
        out_specs=pl.BlockSpec((block_tile, block_size), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block_size), jnp.int32),
        interpret=interpret,
        name=kernel_name("vbyte", None),
    )(payload, counts, bases)
