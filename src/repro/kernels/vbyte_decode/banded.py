"""MXU routing primitives of the decode-tile cores.

The banded core is Stream VByte's default routing; Masked VByte takes it
only by an explicit plan (``plan="banded"`` or a ``chunk`` width), since
its default routes by VPU compaction with no matmul (``kernel.py``,
docs/kernels.md §Compaction routing). Binpack uses :func:`onehot_gather`.

A dense decode core (Stream VByte's unchunked one) routes bytes to output
slots with a ``[T, B, S]`` one-hot (every byte against every output) and
recovers ``out_idx`` with a full ``[S, S]`` triangular matmul — O(S·B)
and O(S²) work for a job the paper does in O(bytes) with pshufb. The
structural fact that makes routing cheap is the **chunk-band invariant**:

    ``out_idx`` is monotone non-decreasing along the byte axis and
    increments by at most 1 per byte, so the bytes of chunk ``c`` (a run of
    ``W`` consecutive byte lanes) can only land in the ``W`` output slots
    ``[chunk_base[c], chunk_base[c] + W)``, where ``chunk_base[c]`` is the
    number of terminator flags in chunks ``0..c-1``.

Routing therefore decomposes into

1. a **chunked prefix sum**: within-chunk exclusive prefix of the
   terminator/length flags via a ``[W, W]`` strict-triangular matmul
   (O(S·W) MACs instead of O(S²)) plus a running int32 sum of the chunk
   totals for the cross-chunk bases,
2. a **banded one-hot scatter**: a ``[T, W, W]`` one-hot per chunk routes
   that chunk's bytes into its W-slot band (O(S·W) MACs per matmul instead
   of O(S·B)),
3. a **cross-chunk combine**: each chunk's band is placed at its
   data-dependent ``chunk_base`` offset by a barrel shift (log₂ static
   shifts + selects, pure VPU) and the overlapped bands are added in int32
   — integers that straddle a chunk boundary get partial sums from both
   chunks landing on the same global slot, and the int32 add recombines
   them exactly (mod 2³²).

**Mosaic shapes.** Everything here works on 2-D ``[T, lanes]`` values: the
chunks are static lane slices in an unrolled Python loop (Mosaic lowers
neither a lane-splitting reshape such as ``[T, S] → [T, nC, W]`` nor a
matmul with two batch dims), and every one-hot contraction has the one
form Mosaic's ``tpu.matmul`` takes for a batched product — a ``[T, out,
in]`` lhs contracted over its last dim against a vector-like ``[T, in]``
rhs (:func:`onehot_scatter`, :func:`onehot_gather`). The same code runs
inside a Pallas kernel body and on the full jnp grid.

**Exactness.** Every matmul passes ``precision=HIGHEST``: on the TPU a
default-precision f32 matmul rounds its operands to bf16, which would
break the f32-exact argument below. With full f32 products, every
per-slot per-chunk accumulation is a sum of at most 5 halfword pieces
(< 2²⁰ ≪ 2²⁴) and every prefix-sum operand is a small count (< 2¹³), so
the f32 results are exact; cross-chunk sums happen after the int32 cast,
wrapping ≡ mod 2³².

:func:`routing_cost` is the tracked FLOP/VMEM model of dense vs banded
routing (``benchmarks/run.py --only decode`` persists it per plan).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# full f32 products on the MXU — every exactness argument in the tile
# cores rests on them (see module docstring)
HIGHEST = lax.Precision.HIGHEST


def normalize_chunk_width(chunk_width: int, block_size: int) -> int:
    """Validate a chunk width: positive multiple of 8, at most block_size."""
    W = int(chunk_width)
    if W <= 0 or W % 8:
        raise ValueError(
            f"chunk_width must be a positive multiple of 8; got {chunk_width}")
    if W > block_size:
        raise ValueError(
            f"chunk_width {W} exceeds block_size {block_size}: a chunk's "
            "output band would be wider than the output itself")
    return W


def kernel_name(format: str, chunk_width: int | None,
                epilogue: str | None = None) -> str:
    """Stable name of a decode ``pallas_call``: its format, its fused
    epilogue if any, and its routing core (``banded_w<W>``; unchunked,
    ``compact`` for vbyte and ``dense`` for streamvbyte; ``gather`` for
    binpack, which has no chunk axis), e.g. ``vbyte_decode_compact`` or
    ``vbyte_fused_bag_sum_banded_w64``."""
    if format == "binpack":
        core = "gather"
    elif chunk_width is None:
        core = "compact" if format == "vbyte" else "dense"
    else:
        core = f"banded_w{int(chunk_width)}"
    stage = "decode" if epilogue is None else f"fused_{epilogue}"
    return f"{format}_{stage}_{core}"


def pad_cols(x: jax.Array, multiple: int) -> jax.Array:
    """Zero-pad the last axis up to a multiple (static concatenate only)."""
    S = x.shape[-1]
    pad = (-S) % multiple
    if not pad:
        return x
    return jnp.concatenate(
        [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)


def split_cols(x: jax.Array, W: int) -> list[jax.Array]:
    """Static ``W``-wide lane slices of ``[T, S]`` (zero-padded to W·nC)."""
    x = pad_cols(x, W)
    return [x[:, c * W:(c + 1) * W] for c in range(x.shape[1] // W)]


def strict_upper(n: int) -> jax.Array:
    """f32 ``[n, n]`` with ``U[k, i] = 1`` iff ``k < i``: ``x @ U`` is the
    exclusive row prefix sum of ``x``."""
    ii = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    jj = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (ii < jj).astype(jnp.float32)


def exact_dot(x: jax.Array, m: jax.Array) -> jax.Array:
    """``x @ m`` for small non-negative int ``x`` and a 0/1 f32 matrix
    ``m``, exact while row sums stay < 2²⁴ (f32, full precision)."""
    return lax.dot(x.astype(jnp.float32), m, precision=HIGHEST,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


_BATCHED_MATVEC = (((2,), (1,)), ((0,), (0,)))  # contract last, batch T


def onehot_scatter(idx: jax.Array, vals: jax.Array, n_out: int) -> jax.Array:
    """``out[t, j] = Σ_i [idx[t, i] == j] · vals[t, i]`` as an MXU matmul.

    ``idx`` int32 ``[T, n_in]``, ``vals`` ``[T, n_in]`` (cast to f32);
    returns f32 ``[T, n_out]``. Exact while every output sums to < 2²⁴.
    Indices outside ``[0, n_out)`` route nowhere.
    """
    T, n_in = idx.shape
    jvec = lax.broadcasted_iota(jnp.int32, (T, n_out, n_in), 1)
    onehot = (idx[:, None, :] == jvec).astype(jnp.float32)  # [T, out, in]
    return lax.dot_general(onehot, vals.astype(jnp.float32), _BATCHED_MATVEC,
                           precision=HIGHEST,
                           preferred_element_type=jnp.float32)


def onehot_gather(idx: jax.Array, vals: jax.Array) -> jax.Array:
    """``out[t, j] = vals[t, idx[t, j]]`` as an MXU matmul (0 when out of
    range). ``vals`` must be < 2²⁴ for the f32 result to be exact."""
    T, n_out = idx.shape
    n_in = vals.shape[1]
    ivec = lax.broadcasted_iota(jnp.int32, (T, n_out, n_in), 2)
    onehot = (idx[:, :, None] == ivec).astype(jnp.float32)  # [T, out, in]
    return lax.dot_general(onehot, vals.astype(jnp.float32), _BATCHED_MATVEC,
                           precision=HIGHEST,
                           preferred_element_type=jnp.float32)


def chunked_prefix(flags: jax.Array, W: int
                   ) -> tuple[list[jax.Array], list[jax.Array]]:
    """Chunked exclusive prefix sum of small non-negative int32 values.

    ``flags`` is ``int32 [T, S]`` (zero-padded to a multiple of ``W``) with
    per-row sums < 2²⁴ (f32-exact). Returns ``(loc, base)``, one entry per
    chunk: ``loc[c]`` int32 ``[T, W]`` is the within-chunk exclusive
    prefix, ``base[c]`` int32 ``[T, 1]`` the sum over all earlier chunks —
    the global exclusive prefix of chunk ``c`` is ``base[c] + loc[c]``.
    Cost: O(S·W) MACs plus nC int32 adds, replacing the dense [S, S]
    triangular matmul's O(S²).
    """
    tri = strict_upper(W)
    run = jnp.zeros((flags.shape[0], 1), jnp.int32)
    loc, base = [], []
    for f in split_cols(flags, W):
        lc = exact_dot(f, tri)
        loc.append(lc)
        base.append(run)
        run = run + lc[:, W - 1:] + f[:, W - 1:]  # + this chunk's total
    return loc, base


def place_band(band: jax.Array, offset: jax.Array,
               out_width: int) -> jax.Array:
    """Place a ``[T, Wb]`` band into a ``[T, out_width]`` row at a
    data-dependent ``[T, 1]`` column offset.

    A barrel shift: ⌈log₂⌉ static zero-fill right-shifts gated per row by
    the offset's bits. Columns past ``out_width`` fall off the end; callers
    guarantee live values stay in range (masked contributions are zero).
    """
    T, Wb = band.shape
    x = band
    if Wb < out_width:
        x = jnp.concatenate(
            [x, jnp.zeros((T, out_width - Wb), x.dtype)], axis=1)
    elif Wb > out_width:
        # a band wider than the output row: columns ≥ out_width can only
        # hold masked zeros (live values index < out_width by contract)
        x = x[:, :out_width]
    off = jnp.clip(offset, 0, out_width)  # [T, 1]
    k = 1
    while k <= out_width:
        bit = (off // k) % 2
        if k < out_width:
            shifted = jnp.concatenate(
                [jnp.zeros((T, k), x.dtype), x[:, : out_width - k]], axis=1)
        else:
            shifted = jnp.zeros_like(x)
        x = jnp.where(bit == 1, shifted, x)
        k *= 2
    return x


def place_bands(bands, offsets, out_width: int) -> jax.Array:
    """Sum ``[T, Wb]`` bands into one ``[T, out_width]`` row, band ``g`` at
    column offset ``offsets[g]`` (``[T, 1]``). The int32 sum is exact mod
    2³²: overlapping bands (integers straddling a chunk boundary)
    recombine here."""
    out = None
    for band, off in zip(bands, offsets):
        placed = place_band(band, off, out_width)
        out = placed if out is None else out + placed
    return out


def banded_scatter_u32(loc, lo, hi, base, out_width: int) -> jax.Array:
    """Banded one-hot MXU scatter of 16-bit-split contributions.

    Per chunk: ``loc`` int32 ``[T, W]`` within-band slot per byte,
    ``lo``/``hi`` int32 ``[T, W]`` halfword contributions (each < 2¹⁶, at
    most 5 per (chunk, slot): f32-exact), ``base`` int32 ``[T, 1]`` band
    offset. Returns int32 ``[T, out_width]`` = lo + (hi << 16), exact mod
    2³². The halves recombine per band before placement: the barrel shift
    only moves values, so placing ``lo + (hi << 16)`` once equals placing
    both halves apart.
    """
    bands = []
    for lc, lo_c, hi_c in zip(loc, lo, hi):
        W = lc.shape[1]
        lo_b = onehot_scatter(lc, lo_c, W).astype(jnp.int32)
        hi_b = onehot_scatter(lc, hi_c, W).astype(jnp.int32)
        bands.append(lo_b + (hi_b << 16))
    return place_bands(bands, base, out_width)


# ---------------------------------------------------------------------------
# FLOP / VMEM model — the tracked "modeled scatter MACs" numbers
# ---------------------------------------------------------------------------
def routing_cost(format: str, *, S: int, B: int, W: int | None,
                 T: int = 8) -> dict:
    """Model the byte→integer routing cost of one decode tile.

    ``mxu_macs`` counts multiply-accumulates of the routing matmuls — the
    prefix-sum triangular contractions, one-hot gathers and the two
    16-bit-split scatter matmuls (the unit the docs quote: the dense cores
    spend ~S·B MACs *per scatter matmul*). ``vpu_ops`` counts the per-lane
    compare/select traffic that is not a contraction: one-hot equality
    tests and the barrel-shift band combine. VMEM counts routing intermediates that scale with the one-hot
    (f32 one-hots, triangular constants, band buffers), not the
    payload/output tiles common to both paths.

    ``W=None`` models the dense core. Numbers are per tile of ``T`` blocks;
    divide by T for per-block, as quoted in docs/kernels.md.
    """
    if format not in ("vbyte", "streamvbyte", "binpack"):
        raise ValueError(f"unknown format {format!r}")
    f32 = 4
    if format == "binpack":
        # binpack has no length scan, so there is no banded variant (W is
        # ignored): the routing is one [T,B,S] one-hot gather realized as
        # two byte-packed contractions, plus pure VPU index/shift math
        mxu = {"window_gather": 2 * T * B * S}  # lo24 + hi16 matmuls
        vpu = {
            "onehot_build": T * B * S,  # byte-offset equality tests
            "shift_mask": 4 * T * B,  # bitpos, shift, recombine, mask
        }
        vmem = {
            "onehot": T * B * S * f32,
            "shifted_copies": 2 * T * S * f32,  # grp012 + grp34 operands
        }
        return {
            "mxu_macs": mxu,
            "mxu_total": sum(mxu.values()),
            "vpu_ops": vpu,
            "vpu_total": sum(vpu.values()),
            "vmem_bytes": vmem,
            "vmem_total": sum(vmem.values()),
        }
    if W is None:
        if format == "vbyte":
            mxu = {
                "prefix_out_idx": T * S * S,      # [T,S]×[S,S] strict tri
                "scatter": 2 * T * S * B,         # lo + hi one-hot matmuls
            }
            vpu = {"onehot_build": T * S * B}
            vmem = {
                "onehot": T * S * B * f32,
                "tri": S * S * f32,
            }
        else:
            C = B // 4
            mxu = {
                "control_expand": T * C * B,      # [T,C]×[C,B] one-hot
                "prefix_starts": T * B * B,       # [T,B]×[B,B] strict tri
                "ends_scatter": T * S * B,        # [T,S,B] end-flag one-hot
                "prefix_out_idx": T * S * S,      # [T,S]×[S,S] strict tri
                "scatter": 2 * T * S * B,
            }
            vpu = {"onehot_build": 2 * T * S * B}  # end flags + scatter
            vmem = {
                "onehot": T * S * B * f32,
                "tri": (B * B + S * S) * f32,
            }
    else:
        nC = -(-S // W)
        Sp = nC * W
        logB = max(1, math.ceil(math.log2(max(2, B + 1))))
        if format == "vbyte":
            mxu = {
                "prefix_out_idx": T * Sp * W,
                "scatter": 2 * T * Sp * W,
            }
            vpu = {
                "onehot_build": T * nC * W * W,
                "band_combine": T * nC * B * logB,
            }
            vmem = {
                "onehot": T * nC * W * W * f32,
                "tri": W * W * f32,
                "bands": T * nC * B * f32,
            }
        else:
            ng = -(-B // W)
            logS = max(1, math.ceil(math.log2(max(2, Sp + 1))))
            mxu = {
                # control expand is a static ×4 broadcast in the banded
                # core — no matmul
                "prefix_starts": T * ng * W * W,
                "ends_scatter": T * ng * 4 * W * W,  # [T,4W,W] one-hots
                "prefix_out_idx": T * Sp * W,
                "scatter": 2 * T * Sp * W,
            }
            vpu = {
                "ends_band_build": T * ng * W * 4 * W,
                "ends_place": T * ng * Sp * logS,
                "onehot_build": T * nC * W * W,
                "band_combine": T * nC * B * logB,
            }
            vmem = {
                "onehot": T * nC * W * W * f32,
                "ends_band": T * ng * 4 * W * f32,
                "tri": W * W * f32,
                "bands": T * nC * B * f32,
            }
    return {
        "mxu_macs": mxu,
        "mxu_total": sum(mxu.values()),
        "vpu_ops": vpu,
        "vpu_total": sum(vpu.values()),
        "vmem_bytes": vmem,
        "vmem_total": sum(vmem.values()),
    }


def routing_reduction(format: str, *, S: int, B: int, W: int,
                      T: int = 8) -> float:
    """Dense-over-banded modeled scatter-MAC ratio (the headline ≥4×)."""
    dense = routing_cost(format, S=S, B=B, W=None, T=T)["mxu_total"]
    banded = routing_cost(format, S=S, B=B, W=W, T=T)["mxu_total"]
    return dense / banded
