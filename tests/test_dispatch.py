"""Dispatch layer: plan resolution and the default route table, the
counts/bases shape contract at the ops boundary, the spans of a traced
call, and the names of the Pallas kernels it launches."""
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import CompressedIntArray
from repro.kernels.vbyte_decode import (dispatch, epilogues,
                                        normalize_block_meta)
from repro.kernels.vbyte_decode.banded import kernel_name
from repro.kernels.vbyte_decode.dispatch import DecodePlan


# ---------------------------------------------------------------------------
# counts/bases shape contract
# ---------------------------------------------------------------------------
def test_normalize_block_meta_accepts_both_shapes():
    flat = jnp.arange(4, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(normalize_block_meta("counts", flat, 4)), np.arange(4))
    np.testing.assert_array_equal(
        np.asarray(normalize_block_meta("counts", flat[:, None], 4)),
        np.arange(4))


@pytest.mark.parametrize("bad_shape", [(3,), (4, 2), (1, 4), (4, 1, 1)])
def test_normalize_block_meta_rejects(bad_shape):
    x = jnp.zeros(bad_shape, jnp.int32)
    with pytest.raises(ValueError, match=r"counts must have shape \[n_blocks\]"):
        normalize_block_meta("counts", x, 4)


@pytest.mark.parametrize("plan", ["jnp", "kernel"])
def test_decoders_accept_column_metadata(rng, plan):
    """[n_blocks, 1] counts/bases decode identically to [n_blocks]."""
    vals = np.sort(rng.integers(0, 2**20, 200)).astype(np.uint64)
    for fmt in ("vbyte", "streamvbyte"):
        arr = CompressedIntArray.encode(vals, format=fmt, differential=True)
        ops = dict(arr.device_operands())
        ref = arr.decode(plan=plan)
        ops["counts"] = ops["counts"][:, None]
        ops["bases"] = ops["bases"][:, None]
        out = dispatch.decode(ops, format=fmt, block_size=128,
                              differential=True, plan=plan)
        np.testing.assert_array_equal(
            np.asarray(out).reshape(-1)[: arr.n].astype(np.uint32), ref)


def test_decode_rejects_wrong_length_counts(rng):
    vals = np.sort(rng.integers(0, 2**20, 200)).astype(np.uint64)
    arr = CompressedIntArray.encode(vals, differential=True)
    ops = dict(arr.device_operands())
    ops["counts"] = ops["counts"][:-1]
    with pytest.raises(ValueError, match="counts must have shape"):
        dispatch.decode(ops, format="vbyte", block_size=128,
                        differential=True, plan="jnp")


# ---------------------------------------------------------------------------
# plan resolution
# ---------------------------------------------------------------------------
def test_resolve_plan_aliases():
    kw = dict(format="vbyte", block_size=128)
    default = dispatch.default_plan("vbyte")
    assert dispatch.resolve_plan("auto", **kw) == default
    assert dispatch.resolve_plan(None, **kw) == default
    assert dispatch.resolve_plan("kernel", **kw) == DecodePlan("pallas", True)
    assert dispatch.resolve_plan("jnp", **kw) == DecodePlan("jnp", True)
    assert dispatch.resolve_plan("ref", **kw) == DecodePlan("ref", False)
    assert dispatch.resolve_plan("unfused", **kw) == DecodePlan(
        default.path, False)
    assert dispatch.resolve_plan("fused", **kw) == DecodePlan(
        default.path, True)
    custom = DecodePlan("pallas", False, 16)
    assert dispatch.resolve_plan(custom, **kw) is custom
    # the plan strings with no caller are gone
    for gone in ("warp-speed", "pallas", "banded", "dense"):
        with pytest.raises(ValueError, match="unknown plan"):
            dispatch.resolve_plan(gone, **kw)
    with pytest.raises(ValueError, match="unknown plan path"):
        DecodePlan("cuda", True)


# the route each format runs on a TPU: the plans of the benchmark's cells
TPU_LABELS = {"vbyte": "pallas_fused_bt8",
              "streamvbyte": "pallas_fused_bt8_w32",
              "binpack": "pallas_fused_bt8"}


@pytest.mark.parametrize("fmt", ["vbyte", "streamvbyte", "binpack"])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_auto_plan_is_the_default_table(monkeypatch, backend, fmt):
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: backend)
    want = (DecodePlan("pallas", True, 8,
                       chunk=32 if fmt == "streamvbyte" else None)
            if backend == "tpu" else DecodePlan("jnp", True))
    assert dispatch.default_plan(fmt) == want
    plan = dispatch.resolve_plan("auto", format=fmt, block_size=128)
    assert plan == want
    if backend == "tpu":
        assert plan.label == TPU_LABELS[fmt]
    # a chunk wider than the block is clamped to it
    small = dispatch.resolve_plan("auto", format=fmt, block_size=16)
    assert small.chunk == (16 if want.chunk else None)


@pytest.mark.parametrize("fmt,path", [("vbyte", "pallas"),
                                      ("binpack", "pallas"),
                                      ("vbyte", "jnp"),
                                      ("streamvbyte", "jnp"),
                                      ("vbyte", "ref")])
def test_a_chunk_on_a_route_without_one_raises(rng, fmt, path):
    """Only Stream VByte's Pallas cores have a chunked route; a plan that
    sets a chunk anywhere else is refused when it is resolved."""
    plan = DecodePlan(path, path == "pallas", chunk=32)
    with pytest.raises(ValueError, match="chunked route"):
        dispatch.resolve_plan(plan, format=fmt, block_size=128)
    vals = np.sort(rng.integers(0, 2**20, 200)).astype(np.uint64)
    arr = CompressedIntArray.encode(vals, format=fmt, differential=True)
    with pytest.raises(ValueError, match="chunked route"):
        dispatch.decode(arr, plan=plan)
    if path == "pallas":  # nor can the fused kernel builder make one
        with pytest.raises(ValueError, match="chunked route"):
            epilogues.fused_decode(arr.device_operands(), {}, format=fmt,
                                   epilogue="stream", block_size=128,
                                   differential=True, chunk_width=32)


def test_epilogue_operand_validation(rng):
    vals = np.sort(rng.integers(0, 512, 64)).astype(np.uint64)
    arr = CompressedIntArray.encode(vals, block_size=32, differential=True)
    ops = arr.device_operands()
    with pytest.raises(ValueError, match="unknown epilogue"):
        dispatch.decode(ops, format="vbyte", block_size=32, differential=True,
                        epilogue="frobnicate")
    with pytest.raises(ValueError, match="missing \\['table'\\]"):
        dispatch.decode(ops, format="vbyte", block_size=32, differential=True,
                        epilogue="bag_sum", epilogue_operands={})
    with pytest.raises(ValueError, match="requires differential=True"):
        dispatch.decode(ops, format="vbyte", block_size=32, differential=False,
                        epilogue="adjacency_rebase",
                        epilogue_operands={"edge_base": jnp.zeros((2, 32),
                                                                 jnp.int32)})


def test_auto_plan_decodes_correctly(rng):
    """End to end: plan='auto' (the default route) is bit-correct."""
    vals = np.sort(rng.integers(0, 512, 100)).astype(np.uint64)
    for fmt in ("vbyte", "streamvbyte", "binpack"):
        arr = CompressedIntArray.encode(vals, format=fmt, block_size=32,
                                        differential=True)
        out = arr.decode(plan="auto")
        np.testing.assert_array_equal(out.astype(np.uint64), vals)


# ---------------------------------------------------------------------------
# spans of a traced call
# ---------------------------------------------------------------------------
@pytest.fixture
def step_clock_telemetry():
    """Telemetry whose clock advances by one on every read, so span bounds
    are exact and distinct."""
    ticks = itertools.count()
    return obs.Telemetry(clock=lambda: float(next(ticks)))


def _ends(s):
    return s["ts"], s["ts"] + s["dur"]


@pytest.mark.parametrize("plan", ["jnp", "kernel"])
def test_decode_span_covers_prepare_then_launch(rng, plan,
                                                step_clock_telemetry):
    vals = np.sort(rng.integers(0, 2**20, 300)).astype(np.uint64)
    arr = CompressedIntArray.encode(vals, block_size=128, differential=True)
    want = arr.decode(plan=plan)  # compiles outside the capture
    with obs.install(step_clock_telemetry):
        out = dispatch.decode(arr, plan=plan)
    np.testing.assert_array_equal(
        np.asarray(out).reshape(-1)[: arr.n].astype(np.uint32), want)

    spans = step_clock_telemetry.tracer.spans
    assert [s["name"] for s in spans] == ["decode.prepare", "decode.launch",
                                          "decode"]  # in closing order
    prepare, launch, parent = spans
    assert parent["parent_id"] is None
    assert prepare["parent_id"] == launch["parent_id"] == parent["span_id"]
    p0, p1 = _ends(parent)
    a0, a1 = _ends(prepare)
    b0, b1 = _ends(launch)
    assert p0 < a0 < a1 < b0 < b1 < p1  # inside the parent, no overlap
    p = dispatch.resolve_plan(plan, format="vbyte", block_size=128)
    assert parent["attrs"] == {"format": "vbyte", "plan": p.label,
                               "epilogue": "stream", "blocks": arr.n_blocks,
                               "chunk": p.chunk, "sharded": False}
    assert prepare["attrs"] == launch["attrs"] == {}
    counters = step_clock_telemetry.registry.snapshot()["metrics"]
    assert [k for k in counters if k.startswith("decode_calls_total")] == [
        f"decode_calls_total{{epilogue=stream,format=vbyte,plan={p.label}}}"]


def test_decode_refused_in_prepare_records_no_launch(rng,
                                                     step_clock_telemetry):
    vals = np.sort(rng.integers(0, 2**20, 200)).astype(np.uint64)
    ops = dict(CompressedIntArray.encode(vals, differential=True)
               .device_operands())
    del ops["bases"]
    with obs.install(step_clock_telemetry):
        with pytest.raises(ValueError, match="missing"):
            dispatch.decode(ops, format="vbyte", block_size=128,
                            differential=True, plan="jnp")
    spans = step_clock_telemetry.tracer.spans
    assert [s["name"] for s in spans] == ["decode.prepare", "decode"]
    assert all(s["attrs"] == {"error": "ValueError"} for s in spans)
    assert not step_clock_telemetry.registry.snapshot()["metrics"]


# ---------------------------------------------------------------------------
# kernel names
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt,chunk,epilogue,want", [
    ("streamvbyte", 64, None, "streamvbyte_decode_banded_w64"),
    ("vbyte", None, None, "vbyte_decode_compact"),
    ("streamvbyte", 32, None, "streamvbyte_decode_banded_w32"),
    ("binpack", None, None, "binpack_decode_gather"),
    ("streamvbyte", 32, "bag_sum", "streamvbyte_fused_bag_sum_banded_w32"),
    ("binpack", None, "membership", "binpack_fused_membership_gather"),
    ("vbyte", None, "membership", "vbyte_fused_membership_compact"),
    ("streamvbyte", None, None, "streamvbyte_decode_dense"),
])
def test_pallas_call_carries_kernel_name(rng, fmt, chunk, epilogue, want):
    """Each decode ``pallas_call`` is named for its format, its fused
    epilogue and its routing core: vbyte's compaction, binpack's gather
    (whatever chunk width the name is asked for), and streamvbyte's dense
    or banded core."""
    vals = np.sort(rng.integers(0, 512, 64)).astype(np.uint64)
    arr = CompressedIntArray.encode(vals, format=fmt, block_size=128,
                                    differential=True)
    extras = {}
    if epilogue == "bag_sum":
        extras = {"table": jnp.ones((512, 8), jnp.float32)}
    elif epilogue == "membership":
        extras = {"probe": jnp.asarray([[3, 5]], jnp.int32)}
    assert kernel_name(fmt, 16 if fmt == "binpack" else chunk,
                       epilogue) == want
    plan = DecodePlan("pallas", epilogue is not None, chunk=chunk)
    jaxpr = jax.make_jaxpr(lambda ops: dispatch.decode(
        ops, format=fmt, block_size=128, differential=True,
        epilogue=epilogue or "stream", epilogue_operands=extras, plan=plan,
        interpret=True))(arr.device_operands())
    assert want in str(jaxpr)
