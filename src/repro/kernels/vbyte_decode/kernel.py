"""Pallas TPU kernel: blocked Masked-VByte decode with fused differential sum.

TPU-native realization of the paper's decoder (docs/kernels.md). Per grid step a
(T, S)-byte VMEM tile (T blocks × S payload bytes — 8×640 = 5120 bytes,
~427× the paper's 12-byte unit, amortizing per-step overhead the way the
paper's 48-byte mask pipeline amortizes pmovmskb latency) is decoded entirely
branch-free:

  * continuation bits via one vectorized compare (pmovmskb analogue),
  * byte→integer routing via a strict-triangular f32 matmul prefix sum
    (replaces the 2^12-entry lookup table),
  * within-integer positions via the ≤5-byte closed form
    (replaces the 170 pshufb control masks),
  * reassembly via a one-hot **MXU** scatter — the systolic array plays the
    role of pshufb (this is the TPU shuffle engine),
  * fused differential prefix sum via triangular matmul (the paper's
    pslldq/paddd doubling tree).

32-bit exactness on an f32 MXU is preserved by splitting every 32-bit word
into 16-bit halves before each matmul: per-output sums stay < 2^24 (f32-exact
at ``precision=HIGHEST``, which every matmul here passes) and are recombined
with wrap-around int32 adds (≡ mod 2^32, i.e. uint32).

``chunk_width=W`` swaps the dense O(S²)+O(S·B) routing for the chunked
banded scatter (``banded.py``): out_idx is monotone with increments ≤ 1,
so a W-byte chunk's outputs live in one W-slot band — O(S·W) routing MACs,
bit-identical output (docs/kernels.md §Banded chunked scatter).

All tensors live in VMEM; block dims are multiples of (8, 128) lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .banded import (banded_scatter_u32, chunked_prefix, exact_dot,
                     kernel_name, normalize_chunk_width, onehot_scatter,
                     split_cols, strict_upper)


def _shift_right(x: jax.Array, k: int) -> jax.Array:
    """x[..., i-k] with zero fill — static slices only (Mosaic-safe)."""
    t, s = x.shape
    return jnp.concatenate([jnp.zeros((t, k), x.dtype), x[:, : s - k]], axis=1)


def _row_cumsum_exact_u32(x: jax.Array, incl_tri: jax.Array) -> jax.Array:
    """Inclusive row cumsum of int32 values, exact mod 2^32 via 16-bit split."""
    lo = exact_dot(x & 0xFFFF, incl_tri)
    hi = exact_dot((x >> 16) & 0xFFFF, incl_tri)
    return lo + (hi << 16)


def decode_tile(payload: jax.Array, counts: jax.Array, *, block_size: int,
                chunk_width: int | None = None) -> tuple[jax.Array, jax.Array]:
    """Decode one VMEM tile of Masked-VByte bytes — the shared decode-tile core.

    ``payload`` is the raw ``uint8 [T, S]`` tile, ``counts`` the ``int32
    [T, 1]`` valid-integer counts. Returns ``(out, valid)``: ``out`` int32
    ``[T, B]`` (bitcast of uint32, masked rows zeroed) and ``valid`` bool
    ``[T, B]``. Pure jnp/lax — callable both from a Pallas kernel body and
    from host-level code; every fused epilogue consumes this contract.

    ``chunk_width=None`` runs the dense O(S²)+O(S·B) routing (full
    triangular prefix matmul + [T, B, S] one-hot scatter). An integer ``W``
    selects the chunked banded-scatter routing (``banded.py``): out_idx is
    monotone and increments ≤1 per byte, so chunk ``c``'s bytes land only
    in slots ``[chunk_base[c], chunk_base[c]+W)`` — O(S·W) MACs, identical
    uint32 output bit-for-bit.
    """
    T, S = payload.shape
    B = block_size

    b = payload.astype(jnp.int32)  # [T, S] bytes
    cont = b >> 7
    end = 1 - cont

    # within-integer byte position (≤ 4): closed form over preceding cont
    # flags — static shifts over the full row, so integers whose bytes
    # straddle a chunk boundary see their true position either way
    c1 = _shift_right(cont, 1)
    c2 = _shift_right(cont, 2)
    c3 = _shift_right(cont, 3)
    c4 = _shift_right(cont, 4)
    pos = c1 * (1 + c2 * (1 + c3 * (1 + c4)))

    contrib = (b & 0x7F) << (7 * pos)  # int32, wraps ≡ uint32

    if chunk_width is None:
        # dense routing: exclusive prefix sum over the full byte axis
        # (out_idx[t,i] = #terminators < i) + full-width one-hot scatter
        out_idx = exact_dot(end, strict_upper(S))
        keep = out_idx < counts  # [T,S] < [T,1]
        contrib = jnp.where(keep, contrib, 0)
        # one-hot MXU scatter: out[t,j] = Σ_i [out_idx[t,i]==j]·contrib[t,i]
        # (masked bytes carry zero, so where they route is irrelevant)
        lo = onehot_scatter(out_idx, contrib & 0xFFFF, B)
        hi = onehot_scatter(out_idx, (contrib >> 16) & 0xFFFF, B)
        out = lo.astype(jnp.int32) + (hi.astype(jnp.int32) << 16)
    else:
        W = normalize_chunk_width(chunk_width, B)
        # chunked prefix: loc = #terminators earlier in the chunk (the
        # within-band slot, < W by construction), base = #terminators in
        # earlier chunks. Padding flags are zeros, so bases are unaffected.
        loc, base = chunked_prefix(end, W)
        lo, hi = [], []
        for lc, bc, cc in zip(loc, base, split_cols(contrib, W)):
            cc = jnp.where(bc + lc < counts, cc, 0)  # keep: out_idx < count
            lo.append(cc & 0xFFFF)
            hi.append((cc >> 16) & 0xFFFF)
        # banded one-hot scatter into W-slot bands + barrel-shift combine;
        # straddling integers recombine via the overlapped int32 band add
        out = banded_scatter_u32(loc, lo, hi, base, B)

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
                        block_size: int, differential: bool,
                        chunk_width: int | None):
    out, valid = decode_tile(payload_ref[...], counts_ref[...],
                             block_size=block_size, chunk_width=chunk_width)
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
    chunk_width: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call wrapper; see ops.vbyte_decode_blocked for the public API."""
    nb, stride = payload.shape
    if nb % block_tile:
        raise ValueError(f"n_blocks={nb} must be a multiple of block_tile={block_tile}")
    grid = (nb // block_tile,)
    kernel = functools.partial(
        _decode_tile_kernel, block_size=block_size, differential=differential,
        chunk_width=chunk_width,
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
        name=kernel_name("vbyte", chunk_width),
    )(payload, counts, bases)
