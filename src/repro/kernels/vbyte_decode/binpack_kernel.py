"""Pallas TPU kernel: blocked binpack decode with fused differential sum.

The two VByte kernels spend their routing budget *finding* integer
boundaries — continuation-bit prefix sums (``kernel.py``) or control-stream
length prefix sums (``stream_kernel.py``). Binpack (Lemire & Boytsov's
binary packing) has no boundaries to find: every integer of a width-``w``
block starts at bit ``j·w``, so this kernel has **no prefix sum over
lengths at all** — the byte→integer routing collapses to one static-index
one-hot gather:

  * bit position ``j·w`` and byte offset ``(j·w) >> 3`` via plain VPU
    integer math on the broadcast width column (no matmul, no scan),
  * the ≤40-bit window holding each value is fetched by ONE ``[T, B, S]``
    one-hot **MXU** gather against five statically-shifted copies of the
    data tile, byte-packed into two f32 operands: ``grp012 = b0 + b1·2^8 +
    b2·2^16 < 2^24`` (f32-exact, single-nonzero one-hot rows) and
    ``grp34 = b3 + b4·2^8 < 2^16`` — two batched matmuls total,
  * extraction is a branch-free ``(lo24 >> s) | (hi16 << (24 - s))`` with
    ``s ∈ 0..7`` (shift amounts stay in 1..24 — no 32-bit-shift hazard)
    masked to ``w`` bits,
  * fused differential prefix sum via the shared triangular-matmul helper.

This is why binpack wins on dense low-width gap blocks: the per-tile MXU
work is two ``[T,B,S]`` contractions and zero routing scans, versus the
VByte kernels' prefix-sum + scatter pipelines. All tensors live in VMEM.
There is no length scan, so no chunk width either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .banded import kernel_name, onehot_gather
from .kernel import prefix_sum_tile

GATHER_BYTES = 5  # shift ≤ 7 bits + width ≤ 32 bits spans at most 5 bytes


def _shift_left_cols(x: jax.Array, k: int) -> jax.Array:
    """x[..., i+k] with zero fill — static slices only (Mosaic-safe)."""
    t, s = x.shape
    if k == 0:
        return x
    return jnp.concatenate([x[:, k:], jnp.zeros((t, k), x.dtype)], axis=1)


def binpack_decode_tile(widths: jax.Array, data: jax.Array, counts: jax.Array,
                        *, block_size: int) -> tuple[jax.Array, jax.Array]:
    """Decode one VMEM tile of binpack-packed bytes.

    ``widths`` is the ``uint8 [T, 1]`` per-block bit-width column, ``data``
    the ``uint8 [T, S]`` packed tile, ``counts`` the ``int32 [T, 1]``
    valid-integer counts. Same ``(out int32 [T, B], valid bool [T, B])``
    contract as ``kernel.decode_tile`` — every fused epilogue plugs in
    unchanged.

    Byte offsets are clamped to ``S - 1``: valid integers end inside
    ``ceil(count·w/8) ≤ S`` bytes by construction, so a clamped read only
    feeds bits the width mask discards or lanes the valid mask zeroes.
    """
    T, S = data.shape
    B = block_size

    w = widths.astype(jnp.int32)  # [T, 1]
    jrow = lax.broadcasted_iota(jnp.int32, (T, B), 1)
    bitpos = jrow * w  # [T, B], < B·32 = 2^12 at B=128
    byte0 = jnp.minimum(bitpos >> 3, S - 1)
    shift = bitpos & 7

    # five statically-shifted data copies, byte-packed into two operands so
    # the 5-byte window costs two MXU contractions instead of five
    b = data.astype(jnp.int32)
    d = [_shift_left_cols(b, k) for k in range(GATHER_BYTES)]
    grp012 = d[0] + (d[1] << 8) + (d[2] << 16)  # < 2^24
    grp34 = d[3] + (d[4] << 8)  # < 2^16

    # one-hot MXU gather: lo24[t,j] = grp012[t, byte0[t,j]] (rows have a
    # single nonzero and operands < 2^24, so full-precision f32 products
    # and accumulation are exact)
    lo24 = onehot_gather(byte0, grp012).astype(jnp.int32)
    hi16 = onehot_gather(byte0, grp34).astype(jnp.int32)

    # lo24 < 2^24 is non-negative (>> is logical); 24 - shift ∈ 17..24;
    # (1 << 31) - 1 wraps to 0x7FFFFFFF in int32 — still the right mask,
    # and w = 32 takes the all-ones branch
    val = (lo24 >> shift) | (hi16 << (24 - shift))
    mask = jnp.where(w >= 32, jnp.int32(-1),
                     (jnp.int32(1) << jnp.minimum(w, 31)) - 1)
    out = val & mask

    valid = jrow < counts  # [T, B] < [T, 1]
    out = jnp.where(valid, out, 0)
    return out, valid


def _binpack_decode_tile_kernel(widths_ref, data_ref, counts_ref, bases_ref,
                                out_ref, *, block_size: int,
                                differential: bool):
    out, valid = binpack_decode_tile(widths_ref[...], data_ref[...],
                                     counts_ref[...], block_size=block_size)
    if differential:
        out = prefix_sum_tile(out, valid, bases_ref[...])
    out_ref[...] = out


def binpack_decode_blocked_pallas(
    widths: jax.Array,  # uint8 [n_blocks, 1]
    data: jax.Array,  # uint8 [n_blocks, stride]
    counts: jax.Array,  # int32 [n_blocks, 1]
    bases: jax.Array,  # int32 [n_blocks, 1] (bitcast of uint32)
    *,
    block_size: int,
    differential: bool,
    block_tile: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call wrapper; see ops.binpack_decode_blocked."""
    nb, stride = data.shape
    if widths.shape != (nb, 1):
        raise ValueError(f"widths shape {widths.shape} != ({nb}, 1)")
    if nb % block_tile:
        raise ValueError(f"n_blocks={nb} must be a multiple of block_tile={block_tile}")
    grid = (nb // block_tile,)
    kernel = functools.partial(
        _binpack_decode_tile_kernel, block_size=block_size,
        differential=differential,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_tile, 1), lambda g: (g, 0)),
            pl.BlockSpec((block_tile, stride), lambda g: (g, 0)),
            pl.BlockSpec((block_tile, 1), lambda g: (g, 0)),
            pl.BlockSpec((block_tile, 1), lambda g: (g, 0)),
        ],
        out_specs=pl.BlockSpec((block_tile, block_size), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block_size), jnp.int32),
        interpret=interpret,
        name=kernel_name("binpack", None),
    )(widths, data, counts, bases)
