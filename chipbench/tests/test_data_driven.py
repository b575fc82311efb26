"""A cell made of new files alone: a configuration, a traffic mix and a
per-layer metric under their directories, plus entries in BENCHMARK.json,
is found by name and run, with no existing file edited."""
import json
import time

from chipbench import harness, tracing

from conftest import ROOT


def test_new_cell_from_new_files_only(tiny_root, cpu, monkeypatch):
    bench_dir = tiny_root / "chipbench"
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}

    cfg = json.loads((bench_dir / "configs/clueweb09b_decode.json")
                     .read_text())
    cfg["name"] = "clueweb09b_decode_mid"
    (bench_dir / "configs/clueweb09b_decode_mid.json").write_text(
        json.dumps(cfg))
    (bench_dir / "traffic/mid_lists.json").write_text(
        json.dumps({"groups": [9], "lists_per_group": 2, "in_flight": 1}))
    (bench_dir / "layer_metrics/calls_in_window.mid.py").write_text(
        "def read(ctx):\n    return ctx.window.work['calls']\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "clueweb09b_decode_mid", "source": "https://example.org",
        "file": "chipbench/configs/clueweb09b_decode_mid.json",
        "reduced": ["vocabulary"], "why": "a cell added by files alone"})
    bench["workloads"].append({
        "name": "decode.mid_lists", "config": "clueweb09b_decode_mid",
        "traffic": "mid_lists", "chips": 1, "why": "data-driven proof"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "decode.long_lists" in m["workloads"]:
            m["workloads"].append("decode.mid_lists")
    bench["per_layer"].append({
        "name": "calls_in_window.mid", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "dispatch",
        "moves": "decode_gint_s", "workloads": ["decode.mid_lists"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    r = harness.run(str(tiny_root), "decode.mid_lists", 8, 0.5, False,
                    t_start=time.perf_counter(), devices=cpu,
                    log=lambda m: None)
    assert r["correct"]
    assert set(r["metrics"]) == {"setup_s", "decode_gint_s",
                                 "index_bits_per_int"}

    # the traced run reads the new metric (a CPU trace has no TPU plane,
    # so the reduction is stood in for)
    monkeypatch.setattr(tracing, "summarize", lambda raw, names:
                        tracing.TraceSummary(1.0, 0.5, 1, {}, {}))
    r = harness.run(str(tiny_root), "decode.mid_lists", 8, 0.5, True,
                    t_start=time.perf_counter(), devices=cpu,
                    log=lambda m: None)
    assert r["metrics"]["calls_in_window.mid"]["value"] == r["attempted"]
    assert r["metrics"]["calls_in_window.mid"]["unit"] == "calls"
    assert set(r["metrics"]) == {"calls_in_window.mid"}

    changed = [p for p, b in before.items() if p.read_bytes() != b]
    assert not changed
    assert json.loads(open(f"{ROOT}/BENCHMARK.json").read()) != bench
