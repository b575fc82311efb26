"""The dispatch layer's span metrics: a traced run reports the whole call
(``dispatch_host_us.decode``) and its two parts, and the parts' readers
stay silent on a program that has no such spans."""
import os
import time
from types import SimpleNamespace

import pytest

from chipbench import harness, tracing

from conftest import ROOT

PARTS = ("dispatch_prepare_us.decode", "dispatch_launch_us.decode")


@pytest.mark.parametrize("workload", ["decode.long_lists",
                                      "decode.short_lists"])
def test_traced_run_reports_prepare_and_launch(tiny_root, cpu, monkeypatch,
                                               workload):
    # a CPU trace has no TPU plane, so the reduction is stood in for
    monkeypatch.setattr(tracing, "summarize", lambda raw, names:
                        tracing.TraceSummary(1.0, 0.5, 1, {}, {}))
    r = harness.run(str(tiny_root), workload, 2**33 + 5, 0.5, True,
                    t_start=time.perf_counter(), devices=cpu,
                    log=lambda m: None)
    assert r["correct"], r["checks"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert all(r["metrics"][k]["unit"] == "us" for k in PARTS)
    prepare, launch = (m[k] for k in PARTS)
    assert 0 < prepare and 0 < launch
    # the parts are children of the whole call's span
    assert prepare + launch <= m["dispatch_host_us.decode"]


def _reader(name):
    return harness._load_module(
        os.path.join(ROOT, "chipbench", "layer_metrics", f"{name}.py"),
        f"chipbench_metric_{name}")


def _span(name, dur):
    return {"type": "span", "name": name, "dur": dur}


@pytest.mark.parametrize("name,span", zip(PARTS, ("decode.prepare",
                                                  "decode.launch")))
def test_part_reader_is_mean_of_its_span(name, span):
    ctx = SimpleNamespace(spans=[_span("decode", 9e-6), _span(span, 2e-6),
                                 _span(span, 4e-6)])
    assert _reader(name).read(ctx) == pytest.approx(3.0)


@pytest.mark.parametrize("name", PARTS)
def test_part_reader_is_silent_without_its_span(name):
    """A program whose ``decode`` span has no children reports nothing."""
    ctx = SimpleNamespace(spans=[_span("decode", 9e-6)])
    assert _reader(name).read(ctx) is None
