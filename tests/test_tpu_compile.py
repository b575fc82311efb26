"""Compile rehearsal for TPU v5e: Mosaic must lower every decode core and
the fused search epilogues at real tile sizes on one chip, and the sharded
search decode on a 4-chip mesh.

Interpret mode (every other kernel test here) runs the kernel bodies as
plain jnp and cannot see what Mosaic refuses: an unsupported matmul form,
a lane-splitting reshape, a primitive without a TPU lowering. These tests
compile for a v5e chip that is described, not attached, so they run on a
CPU-only machine and need no device. They compile and never execute, so
they say nothing about results or speed.

The plan compiled is the one ``dispatch.resolve_plan("auto")`` picks on a
TPU: the tests steer ``jax.default_backend`` to ``"tpu"`` for plan
resolution only, so a change of the TPU default plan is compiled here
automatically.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.vbyte_decode import dispatch, epilogues, ops

N_BLOCKS = 1024
B = 128  # integers per block
STRIDE = 640  # vbyte payload bytes per block (5 bytes × 128)
CELL_STRIDE = 256  # vbyte stride of the ClueWeb09-B decode cells' lists
DATA_STRIDE = 512  # streamvbyte / binpack data bytes per block
W_STRIDE = 256  # impact-stream payload bytes per block (impacts < 2^8)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_plan(monkeypatch):
    """Resolve ``plan="auto"`` as a TPU process would."""
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")

    def resolve(fmt, epilogue="stream"):
        return dispatch.resolve_plan("auto", format=fmt, epilogue=epilogue,
                                     block_size=B)
    return resolve


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _format_operands(sharding, fmt, prefix="", stride=STRIDE):
    """Shapes of ``CompressedIntArray.device_operands()`` at real tiles."""
    u8 = jnp.uint8
    if fmt == "vbyte":
        ops_ = {"payload": _spec(sharding, (N_BLOCKS, stride), u8)}
    elif fmt == "streamvbyte":
        ops_ = {"control": _spec(sharding, (N_BLOCKS, B // 4), u8),
                "data": _spec(sharding, (N_BLOCKS, DATA_STRIDE), u8)}
    else:
        ops_ = {"widths": _spec(sharding, (N_BLOCKS, 1), u8),
                "data": _spec(sharding, (N_BLOCKS, DATA_STRIDE), u8)}
    return {prefix + k: v for k, v in ops_.items()}


def _meta(sharding):
    return {"counts": _spec(sharding, (N_BLOCKS,), jnp.int32),
            "bases": _spec(sharding, (N_BLOCKS,), jnp.uint32)}


def _assert_mosaic_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


DECODE_FNS = {"vbyte": ops.vbyte_decode_blocked,
              "streamvbyte": ops.stream_vbyte_decode_blocked,
              "binpack": ops.binpack_decode_blocked}


@pytest.mark.parametrize("fmt", ["vbyte", "streamvbyte", "binpack"])
def test_default_decode_plan_compiles(one_chip, tpu_plan, fmt):
    plan = tpu_plan(fmt)
    assert plan.path == "pallas" and plan.fused, plan
    operands = {**_format_operands(one_chip, fmt), **_meta(one_chip)}
    lowered = DECODE_FNS[fmt].lower(
        **operands, block_size=B, differential=True,
        block_tile=plan.block_tile, chunk_width=plan.chunk, interpret=False)
    _assert_mosaic_kernel(lowered)


def test_default_vbyte_plan_compiles_at_cell_stride(one_chip, tpu_plan):
    plan = tpu_plan("vbyte")
    assert plan.path == "pallas" and plan.chunk is None, plan
    operands = {**_format_operands(one_chip, "vbyte", stride=CELL_STRIDE),
                **_meta(one_chip)}
    lowered = ops.vbyte_decode_blocked.lower(
        **operands, block_size=B, differential=True,
        block_tile=plan.block_tile, chunk_width=plan.chunk, interpret=False)
    _assert_mosaic_kernel(lowered)


@pytest.mark.parametrize("column", [False, True])
def test_launch_cache_program_compiles_at_cell_stride(one_chip, tpu_plan,
                                                      column):
    # what a repeat dispatch.decode of a decode cell's list runs: one jit
    # of the whole call, [n_blocks, 1] metadata normalised inside it
    plan = tpu_plan("vbyte")
    meta = _meta(one_chip)
    if column:
        meta = {k: _spec(one_chip, (N_BLOCKS, 1), v.dtype)
                for k, v in meta.items()}
    leaves = (_format_operands(one_chip, "vbyte", stride=CELL_STRIDE)
              ["payload"], meta["counts"], meta["bases"])
    fn = dispatch._program(("payload", "counts", "bases"), (), "vbyte", B,
                           True, "stream", plan, False, False)
    _assert_mosaic_kernel(fn.lower(*leaves))


@pytest.mark.parametrize("fmt,chunk", [("vbyte", None), ("vbyte", 32),
                                       ("streamvbyte", None), ("vbyte", 64)])
def test_other_routing_widths_compile(one_chip, fmt, chunk):
    # the unchunked cores (vbyte compaction, streamvbyte dense) decode
    # every impact stream in the weighted epilogues; the banded widths are
    # vbyte's A/B baseline (plan="banded") and autotune candidates
    operands = {**_format_operands(one_chip, fmt), **_meta(one_chip)}
    lowered = DECODE_FNS[fmt].lower(
        **operands, block_size=B, differential=True, block_tile=8,
        chunk_width=chunk, interpret=False)
    _assert_mosaic_kernel(lowered)


def _search_extras(sharding, fmt, epilogue):
    i32 = jnp.int32
    if epilogue.endswith("_rows"):
        extras = {"probe": _spec(sharding, (N_BLOCKS, 1), i32)}
    else:
        extras = {"probe": _spec(sharding, (1, 512), i32)}
    if epilogue.startswith("bm25_accum"):
        extras["impact"] = _spec(sharding, (1, 1), i32)
    if epilogue.startswith("bm25_weighted"):
        w = _format_operands(sharding, fmt, prefix="w_")
        if fmt == "vbyte":
            w["w_payload"] = _spec(sharding, (N_BLOCKS, W_STRIDE), jnp.uint8)
        extras.update(w)
    return extras


@pytest.mark.parametrize("epilogue", ["membership_rows", "bm25_accum_rows",
                                      "bm25_weighted_rows", "membership",
                                      "bm25_accum", "bm25_weighted"])
@pytest.mark.parametrize("fmt", ["vbyte", "binpack"])
def test_fused_search_epilogue_compiles(one_chip, tpu_plan, fmt, epilogue):
    plan = tpu_plan(fmt, epilogue)
    assert plan.path == "pallas" and plan.fused, plan
    operands = {**_format_operands(one_chip, fmt), **_meta(one_chip)}
    lowered = epilogues.fused_decode.lower(
        operands, _search_extras(one_chip, fmt, epilogue), format=fmt,
        epilogue=epilogue, block_size=B, differential=True,
        block_tile=plan.block_tile, chunk_width=plan.chunk, interpret=False)
    _assert_mosaic_kernel(lowered)


COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "collective-permute",
               "reduce-scatter")


@pytest.mark.parametrize("epilogue", ["stream", "membership",
                                      "bm25_weighted"])
def test_sharded_search_decode_compiles(topo, tpu_plan, epilogue):
    # SearchEngine(mesh=...) decodes block-sharded terms under shard_map:
    # on a 4-chip data mesh every device must run its own Mosaic kernel on
    # its own blocks, with no collective in the program
    mesh = Mesh(np.array(topo.devices), ("data",))
    blk = NamedSharding(mesh, P("data", None))
    row = NamedSharding(mesh, P("data"))
    operands = {**_format_operands(blk, "vbyte"),
                "counts": _spec(row, (N_BLOCKS,), jnp.int32),
                "bases": _spec(row, (N_BLOCKS,), jnp.uint32)}
    extras = {}
    if epilogue != "stream":
        extras["probe"] = _spec(NamedSharding(mesh, P()), (1, 512), jnp.int32)
    if epilogue == "bm25_weighted":
        extras["w_payload"] = _spec(blk, (N_BLOCKS, W_STRIDE), jnp.uint8)
    plan = tpu_plan("vbyte", epilogue)
    fn = dispatch._build_sharded_fn(
        mesh, ("data",), "vbyte", epilogue, B, True, plan, False, False,
        tuple(sorted(extras)))
    text = fn.lower(operands, extras).compile().as_text()
    assert "tpu_custom_call" in text
    assert not [c for c in COLLECTIVES if c in text]


RETRIEVAL_ROWS = 8389120  # two-tower item table rows (2^23 items + pad)
RETRIEVAL_BLOCKS = 8192  # 2^20 candidates in 128-int blocks


@pytest.mark.parametrize("table_dtype,nq", [(jnp.bfloat16, 8),
                                            (jnp.float32, 1)])
def test_dot_score_hbm_gather_compiles(one_chip, tpu_plan, table_dtype, nq):
    # the fused dot_score at the served shape: a 2^23-row table left in HBM
    # as a row table, 2^20 candidates per call; Mosaic must accept the
    # span and group DMAs addressed by decoded ids and the rows picked from
    # them at dynamic offsets, and XLA must pass the table to the
    # kernel as it is, with no copy or relayout of it
    d = 256
    plan = tpu_plan("vbyte", "dot_score")
    assert plan.path == "pallas" and plan.fused, plan
    w = d // 2 if table_dtype == jnp.bfloat16 else d
    table = _spec(one_chip, (RETRIEVAL_ROWS, w), jnp.uint32)
    operands = {"payload": _spec(one_chip, (RETRIEVAL_BLOCKS, CELL_STRIDE),
                                 jnp.uint8),
                "counts": _spec(one_chip, (RETRIEVAL_BLOCKS,), jnp.int32),
                "bases": _spec(one_chip, (RETRIEVAL_BLOCKS,), jnp.uint32)}
    extras = {"table": table,
              "query": _spec(one_chip, (nq, d), table_dtype)}
    lowered = epilogues.fused_decode.lower(
        operands, extras, format="vbyte", epilogue="dot_score", block_size=B,
        differential=True, block_tile=plan.block_tile,
        chunk_width=plan.chunk, interpret=False)
    text = lowered.compile().as_text()
    assert "vbyte_fused_dot_score_compact" in text
    # no instruction of the program makes a table-sized value
    made = re.compile(rf"= u32\[{RETRIEVAL_ROWS},{w}\]\S* (?!parameter)")
    assert [ln for ln in text.splitlines() if made.search(ln)] == []
