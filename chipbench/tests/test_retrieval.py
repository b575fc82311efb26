"""The retrieval cell at a tiny size on the CPU: it runs by name and is
correct on the program's answers, each precision control and a planted
fault make ``correct`` false, its two per-layer readers read synthetic spans and
trace, and the bytes function counts what it says.

The tiny cell keeps the registered widths and shrinks only the counts:
items, users, candidates and lists, so the score tolerance is tried at
the widths it was set for."""
import json
import os
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness, tracing, work, work_retrieval
from chipbench import controls_retrieval

from conftest import ROOT

CELL = "retrieval.two_tower_2p20"
TINY = {
    "configs/two_tower_2p23.json": {"n_items": 4096, "n_users": 512,
                                    "n_candidates": 1000},
    "traffic/two_tower_2p20.json": {"corpora": 3, "in_flight": 2},
}


@pytest.fixture
def tiny_retrieval(tmp_path):
    """A checkout-like root with the retrieval cell at a tiny size."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    for rel, change in TINY.items():
        path = tmp_path / "chipbench" / rel
        data = json.loads(path.read_text())
        data.update(change)
        path.write_text(json.dumps(data))
    return tmp_path


def _run(root, cpu, seed, patch=None, trace=False):
    return harness.run(str(root), CELL, seed, 0.3, trace,
                       t_start=time.perf_counter(), devices=cpu,
                       log=lambda m: None, patch=patch)


def test_cell_runs_and_is_correct(tiny_retrieval, cpu):
    r = _run(tiny_retrieval, cpu, 2**33 + 41)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["attempted"] % 3 == 0  # whole passes
    assert r["failed"] == 0 and r["compiles_in_window"] == 0
    cell = harness.resolve(str(tiny_retrieval), CELL)
    assert set(r["metrics"]) == {"setup_s", "decode_gint_s",
                                 "index_bits_per_int"}
    assert {m["name"] for m in cell.end_to_end} == set(r["metrics"])
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert set(r["checks"]) == {"ids_not_in_list", "requests_unchecked",
                                "user_vec_err_max", "item_vec_err_max",
                                "score_err_max", "missed_ids"}
    assert 0 < r["checks"]["user_vec_err_max"]["value"]
    assert 0 < r["checks"]["item_vec_err_max"]["value"]
    fp = r["footprint_bytes"]
    assert fp["kept_for_check"] > 0 and fp["item_table"] > 0
    # 2^12 items: a tiny list's ids are about 2 bytes apart
    assert 8 < r["metrics"]["index_bits_per_int"]["value"] < 16


@pytest.mark.parametrize("name,failing", [
    ("control", "item_vec_err_max"), ("float8_user", "user_vec_err_max"),
    ("bf16_scores", "score_err_max"), ("altered", "ids_not_in_list")])
def test_control_and_fault_make_correct_false(tiny_retrieval, cpu, name,
                                              failing):
    r = _run(tiny_retrieval, cpu, 2**32 + 7,
             patch=controls_retrieval.PATCHES[name])
    assert not r["correct"], r["checks"]
    c = r["checks"][failing]
    assert c["value"] > c["limit"], r["checks"]
    if name == "bf16_scores":  # the layers above the kernel are untouched
        for ok in ("user_vec_err_max", "item_vec_err_max"):
            assert r["checks"][ok]["value"] <= r["checks"][ok]["limit"]


def test_same_seed_same_inputs(tiny_retrieval, cpu):
    cell = harness.resolve(str(tiny_retrieval), CELL)
    a, b, c = (cell.driver.setup(cell.config, cell.traffic, s, devices=cpu,
                                 log=lambda m: None)
               for s in (2**33 + 5, 2**33 + 5, 6))
    for x, y, z in zip(a.lists, b.lists, c.lists):
        assert (x["payload"] == y["payload"]).all()
        assert x["payload"].shape == z["payload"].shape
        assert (x["payload"] != z["payload"]).any()
    assert (a.users_host == b.users_host).all()
    assert (a.hists_host == b.hists_host).all()
    assert (a.users_host != c.users_host).any()
    assert (a.order == b.order).all() and a.sample == b.sample
    assert (a.n_ints == 1000).all()


def test_zipf_ids_follow_their_law():
    drv = harness._load_module(
        os.path.join(ROOT, "chipbench", "drivers", "retrieval.py"),
        "chipbench_driver_retrieval")
    rng = np.random.default_rng(3)
    ids = drv.zipf_ids(rng, 1000, 200_000, 1.0)
    assert ids.min() >= 1 and ids.max() <= 1000
    h = sum(1.0 / k for k in range(1, 1001))
    # id 1 takes 1/H of the draws, id 2 half of that
    assert abs((ids == 1).mean() - 1 / h) < 0.01
    assert abs((ids == 2).mean() - 0.5 / h) < 0.01


def test_traced_run_reports_the_engine_span(tiny_retrieval, cpu,
                                            monkeypatch):
    # a CPU trace has no TPU plane and no Mosaic kernel, so the reduction
    # is stood in for: the roofline finds no kernel and is left out
    monkeypatch.setattr(tracing, "summarize", lambda raw, names:
                        tracing.TraceSummary(1.0, 0.5, 1, {}, {}))
    r = _run(tiny_retrieval, cpu, 2**33 + 9, trace=True)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {
        "retrieve_host_us.retrieval", "dispatch_host_us.retrieval",
        "dispatch_prepare_us.retrieval", "dispatch_launch_us.retrieval",
        "device_idle_share.retrieval"}
    assert r["metrics"]["retrieve_host_us.retrieval"]["value"] > 0
    # one dispatch.decode a retrieve, inside it
    assert (0 < r["metrics"]["dispatch_host_us.retrieval"]["value"]
            < r["metrics"]["retrieve_host_us.retrieval"]["value"])
    assert r["metrics"]["device_idle_share.retrieval"]["value"] == \
        pytest.approx(50.0)


def _reader(name):
    return harness._load_module(
        os.path.join(ROOT, "chipbench", "layer_metrics", f"{name}.py"),
        f"chipbench_metric_{name}")


def test_host_reader_is_the_mean_retrieve_span():
    spans = [{"type": "span", "name": n, "dur": d}
             for n, d in (("retrieve", 3e-6), ("retrieve.score", 2e-6),
                          ("retrieve", 5e-6), ("decode", 9e-6))]
    read = _reader("retrieve_host_us.retrieval").read
    assert read(SimpleNamespace(spans=spans)) == pytest.approx(4.0)
    assert read(SimpleNamespace(spans=spans[3:])) is None


@pytest.mark.parametrize("metric,span", [
    ("dispatch_host_us.retrieval", "decode"),
    ("dispatch_prepare_us.retrieval", "decode.prepare"),
    ("dispatch_launch_us.retrieval", "decode.launch")])
def test_dispatch_readers_are_the_mean_decode_spans(metric, span):
    spans = [{"type": "span", "name": n, "dur": d}
             for n, d in (("retrieve", 30e-6), (span, 3e-6),
                          ("retrieve.score", 20e-6), (span, 5e-6))]
    read = _reader(metric).read
    assert read(SimpleNamespace(spans=spans)) == pytest.approx(4.0)
    assert read(SimpleNamespace(spans=spans[:1])) is None


def test_idle_reader_is_the_trace_idle_share():
    trace = tracing.TraceSummary(2.0, 1.5, 1, {}, {})
    read = _reader("device_idle_share.retrieval").read
    assert read(SimpleNamespace(trace=trace)) == pytest.approx(
        100.0 * trace.idle_share)


def test_roofline_reader_matches_the_kernel_by_name():
    kernel = ('%vbyte_fused_dot_score_compact.1 = (s32[8192,128]{1,0}, '
              'f32[8,1048576]{1,0}) custom-call(%a), '
              'custom_call_target="tpu_custom_call"')
    other = ('%vbyte_decode_compact.2 = s32[8,128]{1,0} custom-call(%a), '
             'custom_call_target="tpu_custom_call"')
    topk = "%topk.3 = (f32[8,100]{1,0}, s32[8,100]{1,0}) sort(%b)"
    trace = tracing.TraceSummary(2.0, 1.5, 1, {kernel: 1.0, other: 0.4,
                                               topk: 0.1}, {})
    ctx = SimpleNamespace(trace=trace, peaks={"hbm_bytes_per_s": 819e9},
                          window=SimpleNamespace(work={
                              "bytes_of_work": 409.5e9}))
    read = _reader("dot_score_roofline.retrieval").read
    assert read(ctx) == pytest.approx(50.0)
    ctx.trace = tracing.TraceSummary(2.0, 1.5, 1, {other: 0.4}, {})
    assert read(ctx) is None  # no such kernel in the program


def test_bytes_of_work_counts_payload_rows_ids_and_scores():
    from chipbench import gen

    ids = np.array([3, 5, 200, 201, 70000], np.uint32)
    payload, counts, bases = gen.vbyte_blocked(ids, 128)
    stored = work.stored_bytes("vbyte", {"payload": payload,
                                         "counts": counts})
    # gaps 3, 2, 195 (2 bytes), 1, 69799 (3 bytes); one block of metadata
    assert stored == 1 + 1 + 2 + 1 + 3 + 8
    got = work_retrieval.bytes_of_work(stored, ids.size, d=256, n_queries=8)
    assert got == stored + 5 * (256 * 2 + 4 + 8 * 4)
    assert work_retrieval.bytes_of_work(
        np.array([stored, 2 * stored]), np.array([5, 10]), d=4,
        n_queries=1, table_itemsize=4).tolist() == [
            stored + 5 * 24, 2 * stored + 10 * 24]
