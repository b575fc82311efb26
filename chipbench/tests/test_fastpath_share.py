"""The dispatch layer's launch-cache metric: a traced decode run reports
the share of calls that hit, and the reader stays silent on a program that
has no such counter."""
import os
import time
from types import SimpleNamespace

import pytest

from chipbench import harness, tracing

from conftest import ROOT

NAME = "dispatch_fastpath_share.decode"


def _reader():
    return harness._load_module(
        os.path.join(ROOT, "chipbench", "layer_metrics", f"{NAME}.py"),
        "chipbench_metric_fastpath_share")


def _counter(value):
    return {"type": "counter", "value": value}


def test_reader_is_hits_over_all_calls():
    ctx = SimpleNamespace(counters={
        "decode_fastpath_total{result=hit}": _counter(297),
        "decode_fastpath_total{result=miss}": _counter(2),
        "decode_fastpath_total{result=bypass}": _counter(1),
        "decode_calls_total{epilogue=stream,format=vbyte,plan=x}":
            _counter(300),
        "plan_cache_total{result=hit}": _counter(5)})
    assert _reader().read(ctx) == pytest.approx(99.0)


@pytest.mark.parametrize("counters", [
    {}, {"decode_calls_total{epilogue=stream,format=vbyte,plan=x}":
         _counter(3)}])
def test_reader_is_silent_without_the_counter(counters):
    assert _reader().read(SimpleNamespace(counters=counters)) is None


@pytest.mark.parametrize("workload", ["decode.long_lists",
                                      "decode.short_lists"])
def test_traced_decode_run_reports_the_share(tiny_root, cpu, monkeypatch,
                                             workload):
    """Set-up warms every shape, so every call of the window hits."""
    monkeypatch.setattr(tracing, "summarize", lambda raw, names:
                        tracing.TraceSummary(1.0, 0.5, 1, {}, {}))
    r = harness.run(str(tiny_root), workload, 2**33 + 7, 0.5, True,
                    t_start=time.perf_counter(), devices=cpu,
                    log=lambda m: None)
    assert r["correct"], r["checks"]
    assert r["metrics"][NAME]["unit"] == "%"
    assert r["metrics"][NAME]["value"] >= 99.0
