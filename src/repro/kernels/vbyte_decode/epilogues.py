"""Pluggable fused decode→consume epilogues for the blocked decode kernels.

The paper's decoder is memory-bound: once the mask/shuffle math is
restructured (kernel.py, stream_kernel.py), the cost is the byte stream in
and the uint32 stream out. Every real consumer in this repo — embedding-bag
over id bags, retrieval dot-scoring, adjacency reconstruction — immediately
gathers/reduces that uint32 stream back out of HBM. Fusing the consumer into
the kernel epilogue removes the decoded stream's HBM round-trip entirely:
the ids live and die in VMEM (the Stream VByte lesson — keep routing
metadata next to the compute — applied one level up the stack).

An :class:`Epilogue` is a pure function over the decode-tile contract

    ``(vals int32 [..., B], valid bool [..., B], **extras) -> out``

plus the Pallas plumbing metadata (extra-operand block specs, output
shapes). The SAME ``apply`` function executes inside the Pallas kernel body
(on a ``[block_tile, B]`` VMEM tile) and on the full ``[n_blocks, B]`` jnp
grid (:func:`apply_grid`, the unfused reference / CPU path) — so the fused
and unfused paths agree bit-exactly by construction.

Registered epilogues:

* ``stream``           — raw decoded integers (the identity epilogue; the
                         fused differential prefix sum of PR 0 is the
                         ``differential=True`` flavor of this).
* ``bag_sum``          — gather-sum embedding bag: one bag per block;
                         ``out[t] = Σ_j valid·table[ids[t,j]]`` in VMEM.
* ``dot_score``        — retrieval scoring: decoded candidate ids gather
                         item vectors and dot against a query; returns
                         ``(ids, scores)`` so the [C, d] candidate-vector
                         matrix never exists in HBM. Its Pallas form keeps
                         the table in HBM and fetches rows by DMA
                         (``gather_kernel.py``), at every table size.
* ``adjacency_rebase`` — GNN adjacency: per-edge ``incl - row_gap_base``
                         subtraction fused into the differential epilogue.
* ``membership``       — inverted-index intersection: decode a postings
                         tile and emit a match bitmap against a sorted
                         probe set resident in VMEM, so the larger list's
                         docids never leave the kernel (repro.index.query).
* ``bm25_accum``       — inverted-index scoring: decode gaps, rebase to
                         docids (the differential prefix sum), and emit
                         each probe candidate's quantized impact
                         contribution; summing the per-block outputs
                         accumulates the term's score exactly (int32).
* ``bm25_weighted``    — per-posting-impact scoring: decode the docid-gap
                         tile AND its aligned quantized-impact tile in the
                         same kernel pass (the impact stream is a second
                         blocked compressed array with identical per-block
                         counts), and emit each probe candidate's exact
                         int32 impact contribution. The weight operands are
                         format-tagged tiled extras — ``w_payload`` (vbyte),
                         ``w_control``/``w_data`` (streamvbyte), or
                         ``w_widths``/``w_data`` (binpack) — so the
                         weighted epilogue works for every format under one
                         name. Drives MaxScore top-k (repro.index.query).
* ``checksum``         — validated decode: the decoded integers plus a
                         per-block position-weighted checksum
                         ``cs[b] = Σ_j vals[b,j]·(2j+1) mod 2^32`` computed
                         in the same tile pass, compared host-side against
                         the encode-time column (repro.robustness.validate)
                         — stream-validation at the cost of one epilogue,
                         not a second HBM round-trip.
* ``membership_rows`` / ``bm25_accum_rows`` / ``bm25_weighted_rows`` —
                         the block-aligned variants:
                         ``probe`` is a **tiled** ``[n_blocks, 1]`` extra
                         (one candidate per gathered block — the skip
                         table already knows the only block that can
                         contain each probe), so the comparison is
                         O(B) per probe instead of O(n_blocks·B). The
                         broadcast variants above remain the path for
                         resident/sharded postings that can't be
                         probe-gathered on the host.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .banded import kernel_name
from .binpack_kernel import binpack_decode_tile
from .gather_kernel import dot_score_pallas, gather_rows, row_table, score_rows
from .kernel import decode_tile, prefix_sum_tile
from .stream_kernel import stream_decode_tile

FORMAT_OPERANDS = {
    "vbyte": ("payload",),
    "streamvbyte": ("control", "data"),
    "binpack": ("widths", "data"),
}


# ---------------------------------------------------------------------------
# epilogue bodies — pure jnp on the decode-tile contract. Reductions are per
# output element (axis-local), and the one float sum over the block axis
# (bag_sum) runs in an explicit order, so tile-vs-grid leading dims don't
# change the result: fused == unfused bit-exactly.
# ---------------------------------------------------------------------------
def _stream_apply(vals, valid):
    return vals


def _bag_sum_apply(vals, valid, *, table):
    T, B = vals.shape
    ids = jnp.where(valid, vals, 0)  # masked slots gather row 0, zeroed below
    vecs = jnp.take(table, ids.reshape(-1), axis=0, mode="clip")
    vecs = vecs.reshape(T, B, -1)
    vecs = jnp.where(valid[:, :, None], vecs, 0)
    # one fixed float accumulation order, slot 0 → B-1, on every path: a
    # ``sum(axis=1)`` lets the compiler pick a tree per shape, and the
    # [block_tile, B] kernel tile and the [n_blocks, B] grid then round
    # differently
    acc = vecs[:, 0]
    for j in range(1, B):
        acc = acc + vecs[:, j]
    return acc  # [T, d]


def _dot_score_apply(vals, valid, *, table, query):
    # table: [V, d] float, or its gather_kernel.row_table (what the Pallas
    # kernel fetches rows from); both give the same vectors
    T, B = vals.shape
    ids = jnp.where(valid, vals, 0)  # pad slots score id 0 (the pad row)
    q = query.reshape(-1, query.shape[-1])  # [n_queries, d]
    s = score_rows(gather_rows(table, ids.reshape(-1), q.shape[1]), q)
    if q.shape[0] == 1:  # single query: scores [T, B] (the original contract)
        return ids, s.reshape(T, B)
    # microbatched queries (the serving engine's bucket): scores [q, T, B],
    # each query's scores contiguous, as the kernel writes them
    return ids, s.reshape(-1, T, B)


def _checksum_apply(vals, valid):
    # cs[t] = Σ_j valid · vals[t,j] · (2j+1)  (mod 2^32). int32 products and
    # sums wrap two's-complement, which is bit-identical to the host's
    # uint32 mod-2^32 arithmetic; odd positional weights make the sum
    # order-sensitive (a swap of two unequal values changes it). Count-0
    # (padding) blocks checksum to 0.
    B = vals.shape[-1]
    w = (2 * jnp.arange(B, dtype=jnp.int32) + 1)[None, :]
    cs = jnp.where(valid, vals * w, 0).sum(axis=1, dtype=jnp.int32)
    return vals, cs[:, None]


def _adjacency_rebase_apply(vals, valid, *, edge_base):
    # u32 wrap-around subtraction ≡ int32 subtraction, bitwise
    return jnp.where(valid, vals - edge_base, 0)


def _membership_apply(vals, valid, *, probe):
    # probe: int32 [1, P] sorted docids, padded with -1 (never matches —
    # docids are < 2^31 so decoded vals are non-negative as int32). The
    # [T, B, P] equality broadcast is the in-VMEM analogue of galloping
    # intersection: every decoded slot is checked against every probe slot
    # on the VPU, and the decoded tile never leaves the kernel.
    p = probe.reshape(-1)
    v = jnp.where(valid, vals, -1)  # masked slots never match
    hit = (v[:, :, None] == p[None, None, :]) & (p[None, None, :] >= 0)
    return hit.any(axis=1).astype(jnp.int32)  # [T, P] match bitmap


def _bm25_accum_apply(vals, valid, *, probe, impact):
    # impact: int32 [1, 1] quantized per-term impact. A docid lives in at
    # most one block, so summing the [n_blocks, P] output over blocks
    # accumulates each candidate's exact int32 score contribution.
    return _membership_apply(vals, valid, probe=probe) * impact.reshape(())


def _membership_rows_apply(vals, valid, *, probe):
    # probe: int32 [T, 1] — block t's single candidate (tiled extra; -1 in
    # padding rows never matches). One O(B) compare per probe, because the
    # host-side skip gallop already routed each probe to its only
    # possible block.
    v = jnp.where(valid, vals, -1)
    hit = (v == probe) & (probe >= 0)  # [T, B], probe broadcasts over B
    return hit.any(axis=1, keepdims=True).astype(jnp.int32)  # [T, 1]


def _bm25_accum_rows_apply(vals, valid, *, probe, impact):
    return (_membership_rows_apply(vals, valid, probe=probe)
            * impact.reshape(()))


def _decode_weight_tile(valid, w_payload=None, w_control=None, w_data=None,
                        w_widths=None):
    """Decode the aligned per-posting weight tile in the same kernel pass.

    The weight stream is a second blocked compressed array whose blocks
    align 1:1 with the main stream, so the main tile's ``valid`` mask IS
    the weight tile's count vector — no extra metadata operands. The
    format discriminator is which operands arrived: ``w_widths`` → binpack,
    ``w_payload`` → vbyte, ``w_control``+``w_data`` → streamvbyte. Always
    decodes with the format's unchunked routing (``chunk_width=None``:
    compaction for vbyte, dense for streamvbyte): the weight stride is
    short (impacts are < 2^impact_bits) and the tile cores are bit-exact
    for any routing geometry.
    """
    counts = valid.astype(jnp.int32).sum(axis=1, keepdims=True)
    B = valid.shape[-1]
    if w_widths is not None and w_data is not None:
        w, _ = binpack_decode_tile(w_widths, w_data, counts,
                                   block_size=B, chunk_width=None)
    elif w_payload is not None:
        w, _ = decode_tile(w_payload, counts, block_size=B, chunk_width=None)
    elif w_control is not None and w_data is not None:
        w, _ = stream_decode_tile(w_control, w_data, counts,
                                  block_size=B, chunk_width=None)
    else:
        raise ValueError(
            "weighted epilogue needs w_payload (vbyte), "
            "w_control + w_data (streamvbyte), or "
            "w_widths + w_data (binpack) extras")
    return jnp.where(valid, w, 0)


def _bm25_weighted_apply(vals, valid, *, probe, w_payload=None,
                         w_control=None, w_data=None, w_widths=None):
    # out[t, i] = Σ_j (vals[t,j] == probe[i]) · weight[t,j] — a docid lives
    # in at most one block, so summing over blocks gives each candidate's
    # exact int32 per-posting-impact contribution.
    w = _decode_weight_tile(valid, w_payload, w_control, w_data, w_widths)
    p = probe.reshape(-1)
    v = jnp.where(valid, vals, -1)
    hit = (v[:, :, None] == p[None, None, :]) & (p[None, None, :] >= 0)
    return (hit.astype(jnp.int32) * w[:, :, None]).sum(axis=1)  # [T, P]


def _bm25_weighted_rows_apply(vals, valid, *, probe, w_payload=None,
                              w_control=None, w_data=None, w_widths=None):
    # probe: int32 [T, 1] — block t's single candidate (see *_rows above).
    w = _decode_weight_tile(valid, w_payload, w_control, w_data, w_widths)
    v = jnp.where(valid, vals, -1)
    hit = (v == probe) & (probe >= 0)  # [T, B]
    return (hit.astype(jnp.int32) * w).sum(axis=1, keepdims=True)  # [T, 1]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def _grid_out(nb, B, bt, dtype):
    return (jax.ShapeDtypeStruct((nb, B), dtype),
            pl.BlockSpec((bt, B), lambda g: (g, 0)))


def _whole_spec(arr):
    """Broadcast operand: the full array is resident every grid step."""
    return pl.BlockSpec(arr.shape, lambda g: (0,) * arr.ndim)


@dataclass(frozen=True)
class Epilogue:
    """One fused decode→consume epilogue (see module docstring)."""

    name: str
    apply: Callable[..., Any]
    extras: tuple[str, ...] = ()
    optional_extras: tuple[str, ...] = ()  # e.g. format-tagged weight operands
    tiled_extras: tuple[str, ...] = ()  # extras sliced per tile like the grid
    hbm_extras: tuple[str, ...] = ()  # extras the kernel reads from HBM by DMA
    requires_differential: bool | None = None  # None = either
    # (n_blocks, block_size, block_tile, extras dict) -> (out_shape, out_spec)
    # — single structs or tuples of structs for multi-output epilogues
    out_info: Callable[..., tuple] = None

    def extra_names(self, extras: dict) -> tuple[str, ...]:
        """Operand order for this call: required, then present optionals."""
        return self.extras + tuple(k for k in self.optional_extras
                                   if k in extras)

    def check_extras(self, extras: dict) -> None:
        missing = [k for k in self.extras if k not in extras]
        allowed = set(self.extras) | set(self.optional_extras)
        extra = [k for k in extras if k not in allowed]
        if missing or extra:
            raise ValueError(
                f"epilogue {self.name!r} takes operands {self.extras} "
                f"(+ optional {self.optional_extras}); "
                f"missing {missing}, unexpected {extra}")

    def check(self, differential: bool, extras: dict) -> None:
        self.check_extras(extras)
        if (self.requires_differential is not None
                and differential != self.requires_differential):
            raise ValueError(
                f"epilogue {self.name!r} requires "
                f"differential={self.requires_differential}")


def _stream_out(nb, B, bt, extras):
    return _grid_out(nb, B, bt, jnp.int32)


def _bag_sum_out(nb, B, bt, extras):
    d = extras["table"].shape[1]
    return (jax.ShapeDtypeStruct((nb, d), extras["table"].dtype),
            pl.BlockSpec((bt, d), lambda g: (g, 0)))


def _checksum_out(nb, B, bt, extras):
    return ((jax.ShapeDtypeStruct((nb, B), jnp.int32),
             jax.ShapeDtypeStruct((nb, 1), jnp.int32)),
            (pl.BlockSpec((bt, B), lambda g: (g, 0)),
             pl.BlockSpec((bt, 1), lambda g: (g, 0))))


def _probe_out(nb, B, bt, extras):
    P = extras["probe"].shape[-1]
    return (jax.ShapeDtypeStruct((nb, P), jnp.int32),
            pl.BlockSpec((bt, P), lambda g: (g, 0)))


def _rows_out(nb, B, bt, extras):
    return (jax.ShapeDtypeStruct((nb, 1), jnp.int32),
            pl.BlockSpec((bt, 1), lambda g: (g, 0)))


EPILOGUES = {
    "stream": Epilogue("stream", _stream_apply, out_info=_stream_out),
    "bag_sum": Epilogue("bag_sum", _bag_sum_apply, extras=("table",),
                        out_info=_bag_sum_out),
    # its Pallas form is gather_kernel.dot_score_pallas (see fused_decode)
    "dot_score": Epilogue("dot_score", _dot_score_apply,
                          extras=("table", "query"), hbm_extras=("table",)),
    "checksum": Epilogue("checksum", _checksum_apply, out_info=_checksum_out),
    "adjacency_rebase": Epilogue(
        "adjacency_rebase", _adjacency_rebase_apply, extras=("edge_base",),
        tiled_extras=("edge_base",), requires_differential=True,
        out_info=_stream_out),
    "membership": Epilogue("membership", _membership_apply,
                           extras=("probe",), out_info=_probe_out),
    "bm25_accum": Epilogue("bm25_accum", _bm25_accum_apply,
                           extras=("probe", "impact"), out_info=_probe_out),
    "membership_rows": Epilogue(
        "membership_rows", _membership_rows_apply, extras=("probe",),
        tiled_extras=("probe",), out_info=_rows_out),
    "bm25_accum_rows": Epilogue(
        "bm25_accum_rows", _bm25_accum_rows_apply,
        extras=("probe", "impact"), tiled_extras=("probe",),
        out_info=_rows_out),
    "bm25_weighted": Epilogue(
        "bm25_weighted", _bm25_weighted_apply, extras=("probe",),
        optional_extras=("w_payload", "w_control", "w_data", "w_widths"),
        tiled_extras=("w_payload", "w_control", "w_data", "w_widths"),
        out_info=_probe_out),
    "bm25_weighted_rows": Epilogue(
        "bm25_weighted_rows", _bm25_weighted_rows_apply, extras=("probe",),
        optional_extras=("w_payload", "w_control", "w_data", "w_widths"),
        tiled_extras=("probe", "w_payload", "w_control", "w_data", "w_widths"),
        out_info=_rows_out),
}


def get_epilogue(name: str) -> Epilogue:
    if name not in EPILOGUES:
        raise ValueError(f"unknown epilogue {name!r}; "
                         f"expected one of {tuple(EPILOGUES)}")
    return EPILOGUES[name]


# ---------------------------------------------------------------------------
# jnp grid path: the unfused reference (and the CPU fused-jit body)
# ---------------------------------------------------------------------------
def apply_grid(epilogue: str, grid_u32: jax.Array, counts: jax.Array,
               extras: dict | None = None):
    """Apply an epilogue to an already-decoded ``uint32 [n_blocks, B]`` grid.

    This is the decode→jnp-consume reference the fused kernels must match
    bit-exactly (same ``apply`` body, full grid instead of VMEM tiles).
    """
    ep = get_epilogue(epilogue)
    extras = extras or {}
    ep.check_extras(extras)
    vals = lax.bitcast_convert_type(grid_u32, jnp.int32)
    B = grid_u32.shape[1]
    valid = (jnp.arange(B, dtype=jnp.int32)[None, :]
             < counts.reshape(-1, 1).astype(jnp.int32))
    return ep.apply(vals, valid, **extras)


# ---------------------------------------------------------------------------
# fused Pallas path: decode-tile core + epilogue in one kernel
# ---------------------------------------------------------------------------
def _tile_decoder(format: str, *, block_size: int, differential: bool,
                  chunk_width: int | None):
    """``decode(*fmt_tiles, counts, bases) -> (vals, valid)``: the format's
    decode-tile core on one VMEM tile, then the differential prefix sum."""
    def decode(*tiles):
        *fmt_tiles, counts, bases = tiles
        if format == "vbyte":
            vals, valid = decode_tile(*fmt_tiles, counts,
                                      block_size=block_size,
                                      chunk_width=chunk_width)
        elif format == "binpack":
            vals, valid = binpack_decode_tile(*fmt_tiles, counts,
                                              block_size=block_size,
                                              chunk_width=chunk_width)
        else:
            vals, valid = stream_decode_tile(*fmt_tiles, counts,
                                             block_size=block_size,
                                             chunk_width=chunk_width)
        if differential:
            vals = prefix_sum_tile(vals, valid, bases)
        return vals, valid
    return decode


def fused_decode_pallas(
    format: str,
    fmt_arrays: tuple,  # ("payload",) or ("control", "data") uint8 arrays
    counts: jax.Array,  # int32 [n_blocks, 1]
    bases: jax.Array,  # int32 [n_blocks, 1] (bitcast of uint32)
    extras: dict,
    *,
    epilogue: str,
    block_size: int,
    differential: bool,
    block_tile: int = 8,
    chunk_width: int | None = None,
    interpret: bool = False,
):
    """Raw pallas_call builder: one pass over (decode tile → epilogue).
    ``dot_score`` has a kernel of its own (:func:`fused_decode`)."""
    ep = get_epilogue(epilogue)
    if ep.out_info is None:
        raise ValueError(f"epilogue {epilogue!r} has no generic fused kernel")
    nb = fmt_arrays[0].shape[0]
    if nb % block_tile:
        raise ValueError(f"n_blocks={nb} must be a multiple of "
                         f"block_tile={block_tile}")
    grid = (nb // block_tile,)
    n_fmt = len(fmt_arrays)
    extra_names = ep.extra_names(extras)

    fmt_specs = [pl.BlockSpec((block_tile, a.shape[1]), lambda g: (g, 0))
                 for a in fmt_arrays]
    meta_specs = [pl.BlockSpec((block_tile, 1), lambda g: (g, 0))] * 2
    extra_specs = [
        pl.BlockSpec((block_tile, extras[k].shape[1]), lambda g: (g, 0))
        if k in ep.tiled_extras else _whole_spec(extras[k])
        for k in extra_names
    ]
    out_shape, out_specs = ep.out_info(nb, block_size, block_tile, extras)
    multi = isinstance(out_shape, tuple)
    decode = _tile_decoder(format, block_size=block_size,
                           differential=differential, chunk_width=chunk_width)

    def kernel(*refs):
        extra_vals = {k: refs[n_fmt + 2 + i][...]
                      for i, k in enumerate(extra_names)}
        out_refs = refs[n_fmt + 2 + len(extra_names):]
        vals, valid = decode(*(r[...] for r in refs[:n_fmt + 2]))
        res = ep.apply(vals, valid, **extra_vals)
        for r, oref in zip(res if multi else (res,), out_refs):
            oref[...] = r

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=fmt_specs + meta_specs + extra_specs,
        out_specs=list(out_specs) if multi else out_specs,
        out_shape=list(out_shape) if multi else out_shape,
        interpret=interpret,
        name=kernel_name(format, chunk_width, epilogue),
    )(*fmt_arrays, counts, bases, *(extras[k] for k in extra_names))


@functools.partial(
    jax.jit,
    static_argnames=("format", "epilogue", "block_size", "differential",
                     "block_tile", "chunk_width", "interpret"),
)
def fused_decode(
    operands: dict,  # format operands incl. counts/bases (device_operands())
    extras: dict,  # epilogue operands, e.g. {"table": ...}
    *,
    format: str,
    epilogue: str,
    block_size: int,
    differential: bool,
    block_tile: int = 8,
    chunk_width: int | None = None,
    interpret: bool | None = None,
):
    """Public fused decode→epilogue entry (jit'd; both formats).

    ``operands`` is exactly ``CompressedIntArray.device_operands()``;
    ``counts``/``bases`` may be ``[n_blocks]`` or ``[n_blocks, 1]`` (see
    ops.normalize_block_meta). Pads ``n_blocks`` to ``block_tile`` (padded
    blocks have count 0) and trims every output back.
    """
    from .ops import _auto_interpret, normalize_block_meta

    ep = get_epilogue(epilogue)
    ep.check(differential, extras)
    if interpret is None:
        interpret = _auto_interpret()
    fmt_names = FORMAT_OPERANDS.get(format)
    if fmt_names is None:
        raise ValueError(f"unknown format {format!r}")
    fmt_arrays = tuple(operands[k] for k in fmt_names)
    nb = fmt_arrays[0].shape[0]
    counts = normalize_block_meta("counts", operands["counts"], nb)
    bases = normalize_block_meta("bases", operands["bases"], nb)

    pad = (-nb) % block_tile
    if pad:
        fmt_arrays = tuple(jnp.pad(a, ((0, pad), (0, 0))) for a in fmt_arrays)
        counts = jnp.pad(counts, ((0, pad),))
        bases = jnp.pad(bases, ((0, pad),))
        extras = {k: (jnp.pad(v, ((0, pad), (0, 0)))
                      if k in ep.tiled_extras else v)
                  for k, v in extras.items()}

    counts2 = counts.astype(jnp.int32)[:, None]
    bases2 = lax.bitcast_convert_type(bases.astype(jnp.uint32), jnp.int32)[:, None]
    if epilogue == "dot_score":
        # the table stays in HBM; rows are fetched by DMA (gather_kernel.py)
        table, query = extras["table"], extras["query"]
        q = query.reshape(-1, query.shape[-1])
        ids, scores = dot_score_pallas(
            _tile_decoder(format, block_size=block_size,
                          differential=differential, chunk_width=chunk_width),
            fmt_arrays, counts2, bases2,
            table if table.dtype == jnp.uint32 else row_table(table), q,
            block_size=block_size, block_tile=block_tile,
            name=kernel_name(format, chunk_width, epilogue),
            interpret=interpret)
        scores = scores.reshape(q.shape[0], -1, block_size)[:, :nb]
        return ids[:nb], scores[0] if q.shape[0] == 1 else scores
    out = fused_decode_pallas(
        format, fmt_arrays, counts2, bases2, extras,
        epilogue=epilogue, block_size=block_size, differential=differential,
        block_tile=block_tile, chunk_width=chunk_width, interpret=interpret,
    )
    if isinstance(out, (tuple, list)):
        return tuple(o[:nb] for o in out)
    return out[:nb]
