"""Pallas TPU kernel: the fused ``dot_score`` epilogue with its table in HBM.

Retrieval scoring decodes a list of candidate ids, gathers their item
vectors from a ``[V, d]`` table and dots them against a query matrix. At a
served size the table is gigabytes, so it cannot be a VMEM-resident operand
of the decode kernel, and Mosaic refuses an in-kernel ``jnp.take`` at any
size. This kernel keeps the table in HBM and fetches the rows it needs by
DMA, as paged attention fetches pages by a block table. Per grid step ``g``
of ``n`` tiles plus one:

1. decode tile ``g``'s ids with the format's decode-tile core (and the
   differential prefix sum), write them out, and copy them, with their
   least and greatest row, from VMEM to SMEM, where the scalar core reads
   them as DMA addresses;
2. fetch their rows into VMEM slot ``g % 2``: where the tile's rows lie
   within ``span_rows`` of each other (a sorted list of dense candidates),
   the whole span, :data:`CHUNK_ROWS` rows a DMA; else each id's group of
   :data:`GROUP_ROWS` rows (one HBM tile), one DMA an id;
3. wait for tile ``g − 1``'s DMAs, in the other slot, pick each id's row
   out of the slot (a row load at a dynamic sublane), and score the rows
   against the ``[n_queries, d]`` query matrix on the MXU, accumulating in
   float32.

So tile ``g``'s rows are in flight while tile ``g − 1`` is scored, and the
``[C, d]`` candidate matrix exists only in VMEM. Only the ids and the
``[n_queries, C]`` scores reach HBM. A span costs a few large DMAs a tile
and reads the rows between the candidates too; groups cost one small DMA a
candidate, which the scalar core issues at some tens of ns each, so a span
is the faster wherever it fits.

The table's HBM layout is :func:`row_table`: ``uint32 [Vp, w]``, ``Vp`` a
whole number of chunks. A 16-bit table packs columns ``j`` and ``j + d/2``
into the low and high halves of word ``j`` (``w = d/2``), so a row is one
sublane of a ``(8, 128)`` tile and unpacking it is two shifts and a lane
concatenation; a 32-bit table keeps its bits (``w = d``). Mosaic moves
whole tiles of 8 rows by DMA, never one row, which is why an id fetches
its group.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK_ROWS = 256  # rows a span DMA moves; a row table has whole chunks
GROUP_ROWS = 8  # rows of one HBM tile of a uint32 table: the least DMA
_SPAN_BYTES = 8 << 20  # a slot's span buffer, per slot
_VMEM_HEADROOM = 8 << 20  # VMEM beside the two slots (tiles, rows, scores)
_ISSUE_UNROLL = 8  # loop bodies per iteration (Mosaic unrolls no partial
# loop, so the unroll is written out)
_NT = (((1,), (1,)), ((), ()))  # contract both operands' last dims


def pack_rows(vecs: jax.Array) -> jax.Array:
    """``[..., d]`` float rows → ``uint32 [..., w]`` words: bfloat16 packs
    column ``j`` with column ``j + d/2`` (``w = d/2``, ``d`` even); any
    other float dtype is stored as float32 bits (``w = d``)."""
    d = vecs.shape[-1]
    if vecs.dtype == jnp.bfloat16:
        if d % 2:
            raise ValueError(f"a bfloat16 row table needs an even width, "
                             f"got d={d}")
        half = lax.bitcast_convert_type(vecs, jnp.uint16).astype(jnp.uint32)
        return half[..., : d // 2] | (half[..., d // 2:] << 16)
    return lax.bitcast_convert_type(vecs.astype(jnp.float32), jnp.uint32)


def span_rows_for(R: int, w: int) -> int:
    """The default span of a tile of ``R`` ids over a table of ``w`` words
    a row: :data:`_SPAN_BYTES` of rows, and at least a group a candidate."""
    rows = _SPAN_BYTES // (4 * w) // CHUNK_ROWS * CHUNK_ROWS
    return max(rows, -(-GROUP_ROWS * R // CHUNK_ROWS) * CHUNK_ROWS)


def padded_rows(V: int) -> int:
    """Rows of the row table of a ``V``-row table: ``V`` rounded up to a
    whole chunk of :data:`CHUNK_ROWS`."""
    return -(-V // CHUNK_ROWS) * CHUNK_ROWS


def row_table(table: jax.Array) -> jax.Array:
    """A ``[V, d]`` float table in the kernel's HBM layout, ``uint32
    [padded_rows(V), w]`` (:func:`pack_rows`); the rows past ``V`` repeat
    row ``V - 1``, so that a clipped id reads the same row from either
    form."""
    V = table.shape[0]
    return jnp.pad(pack_rows(table), ((0, padded_rows(V) - V), (0, 0)),
                   mode="edge")


def unpack_rows(rows: jax.Array, d: int) -> jax.Array:
    """``uint32 [..., w]`` rows of a :func:`row_table` → ``[..., d]``
    float32 (``w = d``) or bfloat16 (``w = d/2``) values, exactly."""
    if rows.shape[-1] == d:
        return lax.bitcast_convert_type(rows, jnp.float32)
    if 2 * rows.shape[-1] != d:
        raise ValueError(f"row width {rows.shape[-1]} fits neither a "
                         f"float32 nor a bfloat16 table of width {d}")
    lo = lax.bitcast_convert_type(rows << 16, jnp.float32)
    hi = lax.bitcast_convert_type(rows & jnp.uint32(0xFFFF0000), jnp.float32)
    return jnp.concatenate([lo.astype(jnp.bfloat16), hi.astype(jnp.bfloat16)],
                           axis=-1)


def gather_rows(table: jax.Array, ids: jax.Array, d: int) -> jax.Array:
    """``table[ids]`` (clipped) as ``[..., d]`` values, from a float
    ``[V, d]`` table or a :func:`row_table`: the jnp path's gather."""
    rows = jnp.take(table, ids, axis=0, mode="clip")
    return unpack_rows(rows, d) if table.dtype == jnp.uint32 else rows


def score_rows(vecs: jax.Array, query: jax.Array) -> jax.Array:
    """``[n_queries, N]`` float32 scores of ``N`` row vectors against a
    ``[n_queries, d]`` query matrix: bfloat16 products accumulated in
    float32 when both are bfloat16, else float32 at full precision."""
    if vecs.dtype == query.dtype == jnp.bfloat16:
        return lax.dot_general(query, vecs, _NT,
                               preferred_element_type=jnp.float32)
    return lax.dot_general(query.astype(jnp.float32), vecs.astype(jnp.float32),
                           _NT, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def dot_score_pallas(decode, fmt_arrays: tuple, counts: jax.Array,
                     bases: jax.Array, table: jax.Array, query: jax.Array, *,
                     block_size: int, block_tile: int, name: str,
                     span_rows: int | None = None, interpret: bool = False):
    """Raw ``pallas_call``: decode → HBM row gather → score, one pass.

    ``decode(*fmt_tiles, counts, bases) -> (ids int32 [T, B], valid)`` is
    the format's decode-tile core (ids zeroed where not valid, so padding
    slots score row 0). ``table`` is a :func:`row_table`, ``query``
    ``[n_queries, d]``. ``span_rows`` (default :func:`span_rows_for`) is
    the widest span of rows a tile fetches whole. Returns ``(ids int32
    [n_blocks, B], scores float32 [n_queries, n_blocks·B])``.
    """
    nb = fmt_arrays[0].shape[0]
    T, B = block_tile, block_size
    R = T * B  # candidates per tile
    if nb % T or nb == 0:
        raise ValueError(f"n_blocks={nb} must be a positive multiple of "
                         f"block_tile={T}")
    Vp, w = table.shape
    if Vp % CHUNK_ROWS:
        raise ValueError(f"a row table has a multiple of {CHUNK_ROWS} rows, "
                         f"got {Vp}")
    nq, d = query.shape
    S = span_rows_for(R, w) if span_rows is None else span_rows
    if S % CHUNK_ROWS or S < GROUP_ROWS * R:
        raise ValueError(f"span_rows={S} must be a multiple of {CHUNK_ROWS} "
                         f"and at least {GROUP_ROWS}·{R}")
    n = nb // T
    n_fmt = len(fmt_arrays)
    chunk_shift = CHUNK_ROWS.bit_length() - 1
    group_shift = GROUP_ROWS.bit_length() - 1

    def tile(g):
        return jnp.minimum(g, n - 1), 0

    def kernel(*refs):
        fmt_refs = refs[:n_fmt]
        counts_ref, bases_ref, q_ref, table_hbm = refs[n_fmt:n_fmt + 4]
        (ids_ref, scores_ref, ids_vmem, ids_smem, plan_smem, buf, rows,
         sems) = refs[n_fmt + 4:]
        g = pl.program_id(0)
        slot = g % 2

        @pl.when(g == 0)
        def _pad_rows():  # row 0, for id 0, after each slot's span
            for k in range(2):
                cp = pltpu.make_async_copy(
                    table_hbm.at[pl.ds(0, GROUP_ROWS)],
                    buf.at[k, pl.ds(S, GROUP_ROWS)], sems.at[2])
                cp.start()
                cp.wait()

        @pl.when(g < n)
        def _fetch():
            ids, _ = decode(*(r[...] for r in fmt_refs), counts_ref[...],
                            bases_ref[...])
            ids_ref[...] = ids
            rows_of = jnp.clip(ids, 0, Vp - 1)
            lo = jnp.min(jnp.where(rows_of > 0, rows_of, Vp), keepdims=True)
            hi = jnp.max(rows_of, keepdims=True)
            lane = lax.broadcasted_iota(jnp.int32, (GROUP_ROWS, B), 1)
            ids_vmem[:T] = rows_of
            ids_vmem[T:] = jnp.where(lane == 0, lo, hi)
            to_smem = pltpu.make_async_copy(ids_vmem, ids_smem.at[slot],
                                            sems.at[2])
            to_smem.start()
            to_smem.wait()

            lo, hi = ids_smem[slot, T, 0], ids_smem[slot, T, 1]
            first = lo >> chunk_shift
            n_chunks = jnp.maximum((hi >> chunk_shift) - first + 1, 0)
            whole = n_chunks * CHUNK_ROWS <= S
            plan_smem[slot, 0] = whole.astype(jnp.int32)
            plan_smem[slot, 1] = n_chunks
            plan_smem[slot, 2] = first * CHUNK_ROWS

            @pl.when(whole)
            def _span():  # the tile's rows lie close: fetch their span
                def issue(c, carry):
                    pltpu.make_async_copy(
                        table_hbm.at[pl.ds(pl.multiple_of(
                            (first + c) * CHUNK_ROWS, CHUNK_ROWS),
                            CHUNK_ROWS)],
                        buf.at[slot, pl.ds(pl.multiple_of(
                            c * CHUNK_ROWS, CHUNK_ROWS), CHUNK_ROWS)],
                        sems.at[slot]).start()
                    return carry

                lax.fori_loop(0, n_chunks, issue, 0)

            @pl.when(jnp.logical_not(whole))
            def _groups():  # far apart: each id's group of rows
                def issue(j, carry):
                    for u in range(_ISSUE_UNROLL):
                        i = j * _ISSUE_UNROLL + u
                        at = ids_smem[slot, i // B, i % B] >> group_shift
                        pltpu.make_async_copy(
                            table_hbm.at[pl.ds(pl.multiple_of(
                                at * GROUP_ROWS, GROUP_ROWS), GROUP_ROWS)],
                            buf.at[slot, pl.ds(pl.multiple_of(
                                i * GROUP_ROWS, GROUP_ROWS), GROUP_ROWS)],
                            sems.at[slot]).start()
                    return carry

                lax.fori_loop(0, R // _ISSUE_UNROLL, issue, 0)

        @pl.when(g > 0)
        def _score():
            prev = 1 - slot
            whole = plan_smem[prev, 0] == 1
            base = plan_smem[prev, 2]

            # the slot's DMAs signal one semaphore, which counts bytes:
            # waits of the sizes started, in any cut, wait for them all
            @pl.when(whole)
            def _():
                def wait(c, carry):
                    pltpu.make_async_copy(
                        table_hbm.at[pl.ds(0, CHUNK_ROWS)],
                        buf.at[prev, pl.ds(0, CHUNK_ROWS)],
                        sems.at[prev]).wait()
                    return carry

                lax.fori_loop(0, plan_smem[prev, 1], wait, 0)

            @pl.when(jnp.logical_not(whole))
            def _():
                pltpu.make_async_copy(
                    table_hbm.at[pl.ds(0, GROUP_ROWS * R)],
                    buf.at[prev, pl.ds(0, GROUP_ROWS * R)],
                    sems.at[prev]).wait()

            def pick(j, carry):
                for u in range(_ISSUE_UNROLL):
                    i = j * _ISSUE_UNROLL + u
                    at = ids_smem[prev, i // B, i % B]
                    row = jnp.where(whole, at - base,
                                    i * GROUP_ROWS + (at & (GROUP_ROWS - 1)))
                    row = jnp.where(at == 0, S, row)
                    rows[pl.ds(i, 1), :] = buf[prev, pl.ds(row, 1), :]
                return carry

            lax.fori_loop(0, R // _ISSUE_UNROLL, pick, 0)
            scores_ref[...] = score_rows(unpack_rows(rows[...], d), q_ref[...])

    buf_bytes = 2 * (S + GROUP_ROWS) * w * 4
    return pl.pallas_call(
        kernel,
        grid=(n + 1,),
        in_specs=[pl.BlockSpec((T, a.shape[1]), tile) for a in fmt_arrays]
        + [pl.BlockSpec((T, 1), tile)] * 2
        + [pl.BlockSpec((nq, d), lambda g: (0, 0)),
           pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((T, B), tile),
                   pl.BlockSpec((nq, R), lambda g: (0, jnp.maximum(g - 1, 0)))],
        out_shape=[jax.ShapeDtypeStruct((nb, B), jnp.int32),
                   jax.ShapeDtypeStruct((nq, nb * B), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((T + GROUP_ROWS, B), jnp.int32),
                        pltpu.SMEM((2, T + GROUP_ROWS, B), jnp.int32),
                        pltpu.SMEM((2, 3), jnp.int32),
                        pltpu.VMEM((2, S + GROUP_ROWS, w), jnp.uint32),
                        pltpu.VMEM((R, w), jnp.uint32),
                        pltpu.SemaphoreType.DMA((3,))],
        # steps run in order: each scores the rows the step before fetched
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buf_bytes + _VMEM_HEADROOM),
        interpret=interpret,
        name=name,
    )(*fmt_arrays, counts, bases, query, table)
