"""Vectorized binpack decoder in JAX — static shift/mask, no scans at all.

Binpack is the degenerate-friendly end of the decode spectrum: where
Masked VByte must *recover* integer boundaries (continuation-bit prefix
sums) and Stream VByte is *told* them (control stream + length prefix
sum), binpack's boundaries are affine — value ``j`` of a width-``w``
block starts at bit ``j·w``. The whole decode is

  bitpos_j = j · w                       (static integer math, no cumsum)
  byte0_j  = bitpos_j >> 3,  shift_j = bitpos_j & 7
  word40_j = data[byte0_j .. byte0_j+4]  (5-byte gather, clamped)
  out_j    = (word40_j >> shift_j) & ((1 << w) - 1)
  differential: out = base + inclusive_cumsum(out)   (fused, as before)

The 40-bit gathered word is carried as two int32 halves to keep every
operation inside exact 32-bit lanes: ``lo24`` (bytes 0–2, < 2^24) and
``hi16`` (bytes 3–4, < 2^16), recombined as
``(lo24 >> s) | (hi16 << (24 - s))`` with ``s ∈ 0..7`` so no shift ever
reaches the 32-bit hazard. Bits wrapped past bit 31 by the ``hi16``
shift are bits ≥ 32 of the value, which cannot exist for ``w ≤ 32``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_U32 = jnp.uint32
GATHER_BYTES = 5  # shift ≤ 7 bits + width ≤ 32 bits spans at most 5 bytes


def block_bit_positions(widths: jax.Array, block_size: int) -> jax.Array:
    """bitpos[b, j] = j · w_b, int32 [n_blocks, block_size] (max 4096·8)."""
    w = jnp.asarray(widths).reshape(-1).astype(jnp.int32)
    j = jnp.arange(block_size, dtype=jnp.int32)
    return j[None, :] * w[:, None]


def gather_words(data: jax.Array, byte0: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Gather the 40-bit window at each byte offset as (lo24, hi16) int32.

    Out-of-range bytes are clamped to the last column; the clamped bytes
    only ever contribute bits the width mask discards (valid values end by
    construction inside ``ceil(count·w/8) ≤ stride`` bytes).
    """
    S = data.shape[-1]
    k = jnp.arange(GATHER_BYTES, dtype=jnp.int32)
    src = jnp.minimum(byte0[..., None] + k, S - 1)
    b = jnp.take_along_axis(
        data, src.reshape(*data.shape[:-1], -1), axis=-1
    ).reshape(*byte0.shape, GATHER_BYTES).astype(jnp.int32)
    lo24 = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
    hi16 = b[..., 3] | (b[..., 4] << 8)
    return lo24, hi16


def extract_values(lo24: jax.Array, hi16: jax.Array, shift: jax.Array,
                   widths: jax.Array) -> jax.Array:
    """(word40 >> shift) & width_mask, in exact int32 lanes → uint32."""
    w = jnp.asarray(widths).reshape(-1).astype(jnp.int32)[:, None]
    # lo24 < 2^24 is non-negative, so >> is a logical shift; 24 - shift
    # stays in 17..24, never a full-width shift
    val = (lo24 >> shift) | (hi16 << (24 - shift))
    # (1 << 31) - 1 wraps to 0x7FFFFFFF in int32 — still the right mask;
    # w = 32 needs all 32 bits, i.e. mask -1 (the shift amount is clamped
    # so the dead branch never shifts by a full lane width)
    mask = jnp.where(w >= 32, jnp.int32(-1),
                     (jnp.int32(1) << jnp.minimum(w, 31)) - 1)
    return (val & mask).astype(_U32)


@functools.partial(jax.jit, static_argnames=("block_size", "differential"))
def decode_blocked(
    widths: jax.Array,
    data: jax.Array,
    counts: jax.Array,
    bases: jax.Array,
    *,
    block_size: int,
    differential: bool,
) -> jax.Array:
    """Vectorized blocked binpack decode: uint32[n_blocks, block_size].

    All blocks decode in parallel at their own width. Zero-padded rows;
    block b row j valid iff j < counts[b].
    """
    B = block_size
    bitpos = block_bit_positions(widths, B)  # [nb, B]
    lo24, hi16 = gather_words(data, bitpos >> 3)
    out = extract_values(lo24, hi16, bitpos & 7, widths)

    j = jnp.arange(B, dtype=jnp.int32)[None, :]
    row_valid = j < counts[:, None].astype(jnp.int32)
    out = jnp.where(row_valid, out, _U32(0))
    if differential:
        out = bases[:, None].astype(_U32) + jnp.cumsum(out, axis=-1, dtype=_U32)
        out = jnp.where(row_valid, out, _U32(0))
    return out


def decode_stream(
    widths: jax.Array,
    data: jax.Array,
    n_max: int,
    *,
    n: jax.Array | int | None = None,
    differential: bool = False,
    base: jax.Array | int = 0,
) -> jax.Array:
    """Decode a single width-``widths[0]`` packed stream to uint32[n_max]."""
    n = n_max if n is None else n
    out = decode_blocked(
        jnp.asarray(widths).reshape(1, 1),
        data[None, :],
        jnp.asarray([n], jnp.int32),
        jnp.asarray([base], _U32),
        block_size=n_max,
        differential=differential,
    )
    return out[0, :n_max]
