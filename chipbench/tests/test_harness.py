"""The harness end to end at tiny sizes on the CPU: each cell runs by name,
its end-to-end metrics come out, and the comparison passes on the
program's own answers."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import harness

from conftest import ROOT

CELLS = ("decode.long_lists", "decode.short_lists")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(tiny_root, cpu, workload):
    r = harness.run(str(tiny_root), workload, 2**33 + 17, 1.0, False,
                    t_start=time.perf_counter(), devices=cpu,
                    log=lambda m: None)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    cell = harness.resolve(str(tiny_root), workload)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"


def test_same_seed_same_inputs(tiny_root, cpu):
    cell = harness.resolve(str(tiny_root), "decode.long_lists")
    a, b, c = (cell.driver.setup(cell.config, cell.traffic, s, devices=cpu,
                                 log=lambda m: None)
               for s in (2**33 + 5, 2**33 + 5, 6))
    for x, y in zip(a.docids, b.docids):
        assert (x == y).all()
    assert (a.order == b.order).all() and a.sample == b.sample
    # another seed: other docids and order, the same lists, strides,
    # shapes and stored bytes
    assert (a.lengths == c.lengths).all() and (a.stored == c.stored).all()
    assert ([x.payload.shape for x in a.arrs]
            == [x.payload.shape for x in c.arrs])
    assert all((x != y).any() for x, y in zip(a.docids, c.docids)
               if x.size > 128)
    assert (a.order != c.order).any()


def test_window_covers_whole_passes(tiny_root, cpu):
    cell = harness.resolve(str(tiny_root), "decode.short_lists")
    state = cell.driver.setup(cell.config, cell.traffic, 2**33 + 9,
                              devices=cpu, log=lambda m: None)
    win = cell.driver.window(state, 0.2)
    assert win.attempted % len(state.arrs) == 0
    passes = win.attempted // len(state.arrs)
    assert win.work["ints"] == passes * int(state.lengths.sum())
    assert set(state.held) == state.sample


def test_sample_holds_every_shape_and_the_longest(tiny_root, cpu):
    cell = harness.resolve(str(tiny_root), "decode.long_lists")
    state = cell.driver.setup(cell.config, cell.traffic, 2**33 + 11,
                              devices=cpu, log=lambda m: None)
    shapes = {a.payload.shape for a in state.arrs}
    assert {state.arrs[j].payload.shape for j in state.sample} == shapes
    assert int(state.lengths.argmax()) in state.sample
    assert len(state.sample) >= min(len(state.arrs), cell.driver.CHECKED)


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "decode.long_lists",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_without_tpu():
    p = _run_py(ROOT, {})
    assert p.returncode != 0
    assert "metrics" not in p.stdout
    assert "no TPU" in p.stderr


def test_run_py_refuses_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    p = _run_py(tmp_path, {})
    assert p.returncode != 0
    assert "metrics" not in p.stdout


def test_benchmark_json_names_resolve():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = harness.resolve(ROOT, w["name"])
        assert cell.per_layer, w["name"]
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layer_metrics", f"{m['name']}.py"))
