"""Pallas TPU kernel: blocked Stream-VByte decode with fused differential sum.

The Masked-VByte kernel (``kernel.py``) spends its first half *recovering*
integer boundaries from continuation bits — the recurrence the paper calls
the expensive part of VByte decoding. Stream VByte stores those boundaries
explicitly as 2-bit codes in a control stream, so this kernel skips the
continuation-bit machinery entirely:

  * control bytes expand to per-integer codes via a one-hot **MXU** matmul
    (each of the 4 packed lanes selects its control byte) + static shifts,
  * integer lengths = code + 1, masked past ``count``,
  * byte→integer routing is a strict-triangular f32 matmul prefix sum over
    the *lengths* (in the VByte kernel the same matmul runs over terminator
    flags — here the operand comes straight from the control stream),
  * each integer's end flag is scattered into byte space by a one-hot MXU
    matmul; the owner of data byte ``i`` is the number of end flags before
    it (a second triangular matmul) and its in-integer position has the
    Masked-VByte closed form over the preceding flags,
  * reassembly reuses the 16-bit-split one-hot MXU scatter: lo halfword
    collects positions 0–1, hi halfword positions 2–3, recombined with a
    wrap-around int32 shift-add (≡ mod 2^32, i.e. uint32) — all per-output
    f32 accumulations stay < 2^16 ≪ 2^24, so the MXU is exact,
  * fused differential prefix sum via the shared triangular-matmul helper.

All tensors live in VMEM; shapes are static; padding control codes are zeros
(code 0 = length 1) so masking by ``count`` is load-bearing, as everywhere
else in this repo.

``chunk_width=W`` replaces the O(S·B) flag/scatter routing above with the
chunked banded scatter: per-integer end flags are banded into
byte space (a W-integer chunk spans ≤ 4W data bytes), after which the
byte→integer machinery is exactly the Masked-VByte banded core — O(S·W)
MACs, bit-identical output (docs/kernels.md §Banded chunked scatter).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .banded import (banded_scatter_u32, chunked_prefix, exact_dot,
                     kernel_name, normalize_chunk_width, onehot_scatter,
                     place_bands, split_cols, strict_upper)
from .kernel import prefix_sum_tile


def _shift_right_fill(x: jax.Array, k: int, fill: int) -> jax.Array:
    """x[..., i-k] with constant fill — static slices only (Mosaic-safe)."""
    t, s = x.shape
    return jnp.concatenate(
        [jnp.full((t, k), fill, x.dtype), x[:, : s - k]], axis=1)


def stream_decode_tile(control: jax.Array, data: jax.Array, counts: jax.Array,
                       *, block_size: int,
                       chunk_width: int | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Decode one VMEM tile of Stream-VByte (control, data) bytes.

    Same ``(out int32 [T, B], valid bool [T, B])`` contract as
    ``kernel.decode_tile`` — the shared decode-tile core every fused
    epilogue plugs into.

    ``chunk_width=None`` runs the dense routing: per-integer end flags
    scattered into byte space by a ``[T, S, B]`` one-hot, a full ``[S, S]``
    triangular prefix for the byte owners, and the two ``[T, B, S]``
    scatter matmuls. An integer ``W`` selects the chunked banded routing:
    per-integer **end flags** are scattered into byte space through narrow
    ``[T, 4W, W]`` one-hot bands per integer chunk (a chunk of W integers
    spans ≤ 4W data bytes), after which the byte→integer machinery is
    exactly the Masked-VByte banded core — chunked prefix of the end flags,
    closed-form in-integer positions, ``[T, W, W]`` banded scatter per byte
    chunk. O(S·W) instead of O(S·B), identical uint32 output bit-for-bit.
    """
    T, C = control.shape
    _, S = data.shape
    B = block_size

    ctrl = control.astype(jnp.int32)  # [T, C]

    # expand control bytes C -> B: column j reads ctrl[:, j // 4].
    if chunk_width is None:
        # dense core: a one-hot f32 matmul plays the role of the unpack
        # shuffle (ctrl < 256: f32-exact)
        cc = lax.broadcasted_iota(jnp.int32, (C, B), 0)
        jj = lax.broadcasted_iota(jnp.int32, (C, B), 1)
        expand = (jj // 4 == cc).astype(jnp.float32)  # [C, B]
        packed = exact_dot(ctrl, expand)  # [T, B]
    else:
        # banded core: the unpack is a static ×4 lane broadcast — zero MACs
        packed = jnp.broadcast_to(ctrl[:, :, None], (T, C, 4)).reshape(T, B)

    jrow = lax.broadcasted_iota(jnp.int32, (T, B), 1)
    code = (packed >> (2 * (jrow % 4))) & 3
    valid_int = jrow < counts  # [T, B] < [T, 1]
    length = jnp.where(valid_int, code + 1, 0)

    if chunk_width is None:
        out = _dense_stream_routing(data, length, counts, S, B)
    else:
        out = _banded_stream_routing(
            data, length, counts,
            W=normalize_chunk_width(chunk_width, B), S=S, B=B)

    out = jnp.where(valid_int, out, 0)
    return out, valid_int


def _dense_stream_routing(data, length, counts, S, B):
    """Dense O(S·B) routing: end flags scattered into byte space, then the
    Masked-VByte dense core (full-row prefix + [T, B, S] one-hot scatter)."""
    # start offset of every integer: exclusive prefix sum over lengths
    # (strict-triangular MXU matmul; sums ≤ 4·B ≪ 2^24, f32-exact)
    starts = exact_dot(length, strict_upper(B))  # [T, B]
    # end flag of integer j at byte starts[j] + length[j] - 1; invalid
    # integers (length 0) carry no flag
    ends = onehot_scatter(starts + length - 1, length > 0, S
                          ).astype(jnp.int32)  # [T, S]
    pos = _in_integer_position(ends)
    # owner of byte i = #end flags strictly before i; bytes past the last
    # valid end flag get out_idx == count ⇒ masked
    out_idx = exact_dot(ends, strict_upper(S))  # [T, S]
    keep = out_idx < counts
    lo, hi = _halfword_contributions(data.astype(jnp.int32), pos, keep)
    lo_sum = onehot_scatter(out_idx, lo, B).astype(jnp.int32)
    hi_sum = onehot_scatter(out_idx, hi, B).astype(jnp.int32)
    return lo_sum + (hi_sum << 16)  # [T, B]


def _in_integer_position(ends):
    """Byte position inside its integer: closed form over preceding
    non-end flags (lengths ≤ 4 ⇒ three terms); byte -1 counts as an end."""
    e1 = _shift_right_fill(ends, 1, 1)
    e2 = _shift_right_fill(ends, 2, 1)
    e3 = _shift_right_fill(ends, 3, 1)
    return (1 - e1) * (1 + (1 - e2) * (1 + (1 - e3)))


def _halfword_contributions(byte, pos, keep):
    """16-bit split before the MXU scatter: positions 0-1 build the low
    halfword, positions 2-3 the high one."""
    lo = jnp.where(keep & (pos < 2), byte << (8 * pos), 0)
    hi = jnp.where(keep & (pos >= 2), byte << (8 * (pos - 2)), 0)
    return lo, hi


def _banded_stream_routing(data, length, counts, *, W, S, B):
    """Chunked O(S·W) routing via end flags in byte space.

    Stage 1 — integer-axis chunking: chunked prefix of the lengths gives
    every integer's start; an integer chunk of W integers spans at most
    4W data bytes, so each integer's end flag (at ``start+len-1``) lands
    inside a [4W]-wide band anchored at the chunk's first start. The bands
    are summed into byte space at their (data-dependent) anchors by the
    shared barrel-shift placement.

    Stage 2 — byte-axis chunking: with end flags materialized, the owner
    of byte i is the number of flags strictly before i and the in-integer
    position has the Masked-VByte closed form (lengths ≤ 4 close it after
    three terms), so the chunked prefix + banded one-hot scatter of
    ``banded.py`` finish the job exactly as in ``kernel.decode_tile``.
    """
    # integer starts via chunked prefix over the lengths (B axis, padded to
    # a chunk multiple; padding lengths are zero so starts stay == total)
    loc_l, base_l = chunked_prefix(length, W)
    Sp = S + ((-S) % W)
    bands, anchors = [], []
    for lc, bc, len_c in zip(loc_l, base_l, split_cols(length, W)):
        starts = bc + lc  # [T, W]
        anchor = starts[:, :1]  # [T, 1] the chunk's first start
        # end flag of integer j sits at starts[j] + length[j] - 1, inside
        # the chunk's [4W] band; invalid ints (length 0) carry no flag
        local_end = starts + len_c - 1 - anchor
        bands.append(onehot_scatter(local_end, (len_c > 0), 4 * W)
                     .astype(jnp.int32))  # [T, 4W]
        anchors.append(anchor)
    ends = place_bands(bands, anchors, Sp)  # [T, Sp] end flags

    pos = _in_integer_position(ends)  # [T, Sp]

    # owner of byte i = #end flags strictly before i (chunked prefix);
    # bytes past the last valid end flag get out_idx == count ⇒ masked
    loc_b, base_b = chunked_prefix(ends, W)
    byte = data.astype(jnp.int32)
    lo, hi = [], []
    for lc, bc, pc, yc in zip(loc_b, base_b, split_cols(pos, W),
                              split_cols(byte, W)):
        lo_c, hi_c = _halfword_contributions(yc, pc, bc + lc < counts)
        lo.append(lo_c)
        hi.append(hi_c)
    return banded_scatter_u32(loc_b, lo, hi, base_b, B)


def _stream_decode_tile_kernel(control_ref, data_ref, counts_ref, bases_ref,
                               out_ref, *, block_size: int, differential: bool,
                               chunk_width: int | None):
    out, valid = stream_decode_tile(control_ref[...], data_ref[...],
                                    counts_ref[...], block_size=block_size,
                                    chunk_width=chunk_width)
    if differential:
        out = prefix_sum_tile(out, valid, bases_ref[...])
    out_ref[...] = out


def stream_decode_blocked_pallas(
    control: jax.Array,  # uint8 [n_blocks, block_size // 4]
    data: jax.Array,  # uint8 [n_blocks, data_stride]
    counts: jax.Array,  # int32 [n_blocks, 1]
    bases: jax.Array,  # int32 [n_blocks, 1] (bitcast of uint32)
    *,
    block_size: int,
    differential: bool,
    block_tile: int = 8,
    chunk_width: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call wrapper; see ops.stream_vbyte_decode_blocked."""
    nb, C = control.shape
    _, stride = data.shape
    if C * 4 != block_size:
        raise ValueError(f"control width {C} != block_size/4 = {block_size // 4}")
    if nb % block_tile:
        raise ValueError(f"n_blocks={nb} must be a multiple of block_tile={block_tile}")
    grid = (nb // block_tile,)
    kernel = functools.partial(
        _stream_decode_tile_kernel, block_size=block_size,
        differential=differential, chunk_width=chunk_width
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_tile, C), lambda g: (g, 0)),
            pl.BlockSpec((block_tile, stride), lambda g: (g, 0)),
            pl.BlockSpec((block_tile, 1), lambda g: (g, 0)),
            pl.BlockSpec((block_tile, 1), lambda g: (g, 0)),
        ],
        out_specs=pl.BlockSpec((block_tile, block_size), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block_size), jnp.int32),
        interpret=interpret,
        name=kernel_name("streamvbyte", chunk_width),
    )(control, data, counts, bases)
