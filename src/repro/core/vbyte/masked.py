"""MASKED VBYTE, adapted to TPU — the paper's contribution in vectorized JAX.

The x86 algorithm (paper §IV) is: pmovmskb extracts 16 continuation bits →
a 12-bit mask slice indexes a 2^12 table of (consumed bytes, shuffle index) →
pshufb routes payload bytes to fixed lanes → masked shifts + ORs reassemble
integers → a SIMD prefix sum fuses differential decoding.

TPU has neither pshufb nor pmovmskb, and scalar table lookups serialize
(DESIGN.md §2). The transferable insight is *branch-free, data-parallel mask
processing*; here every step is an arithmetic identity over whole byte tiles:

  continuation mask   c_i   = byte_i >> 7                 (the pmovmskb analogue,
                                                           kept vectorized, never packed)
  terminator flag     end_i = 1 - c_i
  output index        out_idx_i = Σ_{k<i} end_k           (exclusive prefix sum —
                                                           replaces the 2^12 lookup)
  in-integer position pos_i = c_{i-1}(1 + c_{i-2}(1 + c_{i-3}(1 + c_{i-4})))
                                                          (closed form: ≤5 bytes/int,
                                                           replaces the 170 pshufb masks)
  contribution        contrib_i = (byte_i & 0x7F) << 7·pos_i
  reassembly          out_j = Σ_{i: out_idx_i = j} contrib_i   (segment-sum / one-hot
                                                                matmul — the MXU is the
                                                                TPU's shuffle unit)
  differential        out = base + inclusive_cumsum(out)  (fused, as in the paper)

All shapes are static; tail/padding bytes are masked via ``out_idx < count``
(padding zero bytes *look like* terminators of 0, so masking is load-bearing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_U32 = jnp.uint32


def continuation_bits(data: jax.Array) -> jax.Array:
    """Vectorized pmovmskb analogue: 1 where the byte continues, else 0."""
    return (data.astype(_U32) >> 7).astype(jnp.int32)


def in_integer_positions(cont: jax.Array) -> jax.Array:
    """pos_i = number of consecutive continuation bytes immediately before i.

    VByte(32-bit) integers span ≤5 bytes so the recurrence closes after four
    shifted terms — static shifts only, no scan (Mosaic/VPU friendly).
    """
    def shifted(k: int) -> jax.Array:
        pad = [(0, 0)] * (cont.ndim - 1) + [(k, 0)]
        return jnp.pad(cont, pad)[..., : cont.shape[-1]]

    c1, c2, c3, c4 = shifted(1), shifted(2), shifted(3), shifted(4)
    return c1 * (1 + c2 * (1 + c3 * (1 + c4)))


def byte_contributions(data: jax.Array, pos: jax.Array) -> jax.Array:
    """(byte & 0x7F) << 7*pos, as uint32 (wraps mod 2^32 like the paper's 32-bit lanes)."""
    return (data.astype(_U32) & _U32(0x7F)) << (7 * pos).astype(_U32)


def decode_stream(
    data: jax.Array,
    n_max: int,
    *,
    nbytes: jax.Array | int | None = None,
    differential: bool = False,
    base: jax.Array | int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Vectorized decode of a single VByte stream.

    Args:
      data: uint8[S] byte stream (may be zero-padded past ``nbytes``).
      n_max: static output capacity.
      nbytes: number of valid bytes (defaults to all of ``data``).
      differential: fuse the prefix sum over decoded gaps (paper §IV last ¶).
      base: carry-in absolute value for differential decoding.

    Returns:
      (out uint32[n_max] zero-padded, n_decoded int32)
    """
    S = data.shape[-1]
    idx = jnp.arange(S, dtype=jnp.int32)
    valid_byte = idx < (jnp.int32(S) if nbytes is None else jnp.asarray(nbytes, jnp.int32))

    cont = continuation_bits(data) * valid_byte
    end = (1 - cont) * valid_byte
    out_idx = jnp.cumsum(end, dtype=jnp.int32) - end  # exclusive prefix sum
    pos = in_integer_positions(cont)
    contrib = byte_contributions(data, pos)

    n_decoded = jnp.minimum(jnp.sum(end, dtype=jnp.int32), jnp.int32(n_max))
    keep = valid_byte & (out_idx < n_max)
    contrib = jnp.where(keep, contrib, _U32(0))
    ids = jnp.where(keep, out_idx, n_max - 1 if n_max else 0)

    out = jax.ops.segment_sum(contrib, ids, num_segments=n_max)

    j = jnp.arange(n_max, dtype=jnp.int32)
    out = jnp.where(j < n_decoded, out, _U32(0))
    if differential:
        out = jnp.asarray(base, _U32) + jnp.cumsum(out, dtype=_U32)
        out = jnp.where(j < n_decoded, out, _U32(0))
    return out, n_decoded


@functools.partial(jax.jit, static_argnames=("block_size", "differential"))
def decode_blocked(
    payload: jax.Array,
    counts: jax.Array,
    bases: jax.Array,
    *,
    block_size: int,
    differential: bool,
) -> jax.Array:
    """Vectorized decode of the blocked layout: uint32[n_blocks, block_size].

    All blocks decode in parallel (the SPMD adaptation of the paper's
    sequential 48-byte mask pipeline). Zero-padded rows; block b row j valid
    iff j < counts[b].
    """
    nb, S = payload.shape
    B = block_size

    cont = continuation_bits(payload)  # padding zeros ⇒ cont=0 (handled by count mask)
    end = 1 - cont
    out_idx = jnp.cumsum(end, axis=-1, dtype=jnp.int32) - end
    pos = in_integer_positions(cont)
    contrib = byte_contributions(payload, pos)

    keep = out_idx < counts[:, None].astype(jnp.int32)
    contrib = jnp.where(keep, contrib, _U32(0))
    ids_in_block = jnp.minimum(out_idx, B - 1)
    flat_ids = (jnp.arange(nb, dtype=jnp.int32)[:, None] * B + ids_in_block).reshape(-1)
    out = jax.ops.segment_sum(
        contrib.reshape(-1), flat_ids, num_segments=nb * B
    ).reshape(nb, B)

    j = jnp.arange(B, dtype=jnp.int32)[None, :]
    row_valid = j < counts[:, None].astype(jnp.int32)
    out = jnp.where(row_valid, out, _U32(0))
    if differential:
        out = bases[:, None].astype(_U32) + jnp.cumsum(out, axis=-1, dtype=_U32)
        out = jnp.where(row_valid, out, _U32(0))
    return out


def count_integers(data: jax.Array, nbytes: jax.Array | int | None = None) -> jax.Array:
    """Number of complete integers in a stream = number of terminator bytes."""
    S = data.shape[-1]
    valid = (
        jnp.ones((S,), jnp.int32)
        if nbytes is None
        else (jnp.arange(S, dtype=jnp.int32) < jnp.asarray(nbytes, jnp.int32)).astype(jnp.int32)
    )
    return jnp.sum((1 - continuation_bits(data)) * valid, dtype=jnp.int32)
