"""Vectorized Stream-VByte decoder in JAX — no continuation-bit scan at all.

Where ``masked.py`` recovers integer boundaries from the payload itself
(continuation bits → prefix sums → closed-form positions), the Stream VByte
format hands the decoder the boundaries for free: 2-bit codes in a separate
control stream *are* the lengths. The whole decode collapses to

  code_j    = (control[j//4] >> 2*(j%4)) & 3          (static gather/unpack)
  len_j     = (code_j + 1) · [j < count]              (tail masking)
  start_j   = Σ_{k<j} len_k                           (exclusive prefix sum)
  out_j     = Σ_{k<len_j} data[start_j + k] << 8k     (≤4-byte gather, full
                                                       8 bits per byte)
  differential: out = base + inclusive_cumsum(out)    (fused, as before)

No per-byte data-dependent masks, no 2^12 tables, no pshufb analogue — the
control stream replaces all of it, which is exactly why the format decodes
faster than Masked VByte on every architecture the Stream VByte paper
measures. Padding control codes are zeros (code 0 = length 1), so masking by
``j < count`` is load-bearing just like in the VByte path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_U32 = jnp.uint32
MAX_BYTES_PER_INT = 4


def control_codes(control: jax.Array, block_size: int) -> jax.Array:
    """Unpack 2-bit codes: uint8[..., B//4] -> int32[..., B] (LSB-first)."""
    j = jnp.arange(block_size, dtype=jnp.int32)
    packed = jnp.take(control, j // 4, axis=-1).astype(jnp.int32)
    return (packed >> (2 * (j % 4))) & 3


def integer_lengths(codes: jax.Array, counts: jax.Array | None = None) -> jax.Array:
    """Data-byte lengths per integer (1..4), zeroed past ``counts``."""
    lens = codes + 1
    if counts is None:
        return lens
    j = jnp.arange(codes.shape[-1], dtype=jnp.int32)
    return jnp.where(j < jnp.asarray(counts, jnp.int32)[..., None], lens, 0)


def start_offsets(lengths: jax.Array) -> jax.Array:
    """Exclusive prefix sum of lengths: each integer's first data byte."""
    return jnp.cumsum(lengths, axis=-1, dtype=jnp.int32) - lengths


def gather_values(data: jax.Array, starts: jax.Array, lengths: jax.Array) -> jax.Array:
    """Reassemble uint32 values: out_j = Σ_{k<len_j} data[start_j+k] << 8k."""
    S = data.shape[-1]
    k = jnp.arange(MAX_BYTES_PER_INT, dtype=jnp.int32)
    src = jnp.minimum(starts[..., None] + k, S - 1)  # clamp: masked below
    flat = jnp.take_along_axis(
        data, src.reshape(*data.shape[:-1], -1), axis=-1
    ).reshape(*starts.shape, MAX_BYTES_PER_INT).astype(_U32)
    used = k < lengths[..., None]
    contrib = jnp.where(used, flat << (8 * k).astype(_U32), _U32(0))
    return contrib.sum(axis=-1, dtype=_U32)


@functools.partial(jax.jit, static_argnames=("block_size", "differential"))
def decode_blocked(
    control: jax.Array,
    data: jax.Array,
    counts: jax.Array,
    bases: jax.Array,
    *,
    block_size: int,
    differential: bool,
) -> jax.Array:
    """Vectorized blocked Stream-VByte decode: uint32[n_blocks, block_size].

    All blocks decode in parallel. Zero-padded rows; block b row j valid iff
    j < counts[b].
    """
    B = block_size
    codes = control_codes(control, B)  # [nb, B]
    lens = integer_lengths(codes, counts)
    starts = start_offsets(lens)
    out = gather_values(data, starts, lens)

    j = jnp.arange(B, dtype=jnp.int32)[None, :]
    row_valid = j < counts[:, None].astype(jnp.int32)
    out = jnp.where(row_valid, out, _U32(0))
    if differential:
        out = bases[:, None].astype(_U32) + jnp.cumsum(out, axis=-1, dtype=_U32)
        out = jnp.where(row_valid, out, _U32(0))
    return out


def decode_stream(
    control: jax.Array,
    data: jax.Array,
    n_max: int,
    *,
    n: jax.Array | int | None = None,
    differential: bool = False,
    base: jax.Array | int = 0,
) -> jax.Array:
    """Decode a single (control, data) stream pair to uint32[n_max].

    ``control`` must hold at least ``ceil(n_max/4)`` bytes (zero-pad past the
    valid region); ``n`` is the number of valid integers (default: n_max).
    """
    n = n_max if n is None else n
    out = decode_blocked(
        control[None, : -(-n_max // 4)],
        data[None, :],
        jnp.asarray([n], jnp.int32),
        jnp.asarray([base], _U32),
        block_size=-(-n_max // 4) * 4,
        differential=differential,
    )
    return out[0, :n_max]
