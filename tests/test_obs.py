"""Telemetry subsystem: null fast path, metrics merge algebra, span trees,
exporter round-trips, and the end-to-end wiring contracts.

The load-bearing guarantees (docs/observability.md):

* With nothing installed, ``trace()`` returns the shared ``NULL_SPAN``
  singleton — no allocation, no recording — and query results are
  bit-identical with telemetry on, off, and after uninstall.
* Registry merges are associative/commutative (histograms merge bucket
  counts, counters add, gauges last-write), so per-shard registries fold
  in any order.
* A ``mode="maxscore"`` topk produces one span tree whose stage durations
  sum to the root wall time, with decode spans carrying
  (format, plan, epilogue) attribution.
* ``QueryStats.merge`` iterates dataclass fields — adding a field of an
  unmergeable type fails loudly instead of silently dropping counts.
"""
import json

import numpy as np
import pytest

from repro import obs
from repro.obs.exporters import (chrome_trace_events, parse_prometheus,
                                 read_chrome_trace, read_jsonl)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with no telemetry installed."""
    obs.uninstall()
    yield
    obs.uninstall()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# ---------------------------------------------------------------------------
# null fast path
# ---------------------------------------------------------------------------
def test_null_recorder_is_identity_singleton():
    s1 = obs.trace("decode", format="vbyte")
    s2 = obs.trace("anything")
    assert s1 is obs.NULL_SPAN and s2 is obs.NULL_SPAN  # no allocation
    assert not s1  # falsy: `if span:` guards attr computation
    with s1 as sp:
        sp.set(a=1).event("x", b=2)  # all no-ops, chainable, re-entrant
    # metric helpers are no-ops too
    obs.counter_inc("c", 5, lbl="x")
    obs.histogram_observe("h", 0.5)
    assert obs.installed() is None


def test_install_uninstall_and_nesting():
    t1, t2 = obs.Telemetry(), obs.Telemetry()
    with obs.install(t1):
        assert obs.installed() is t1
        with obs.install(t2):
            assert obs.installed() is t2
            with obs.trace("inner"):
                pass
        assert obs.installed() is t1  # nested install restored the outer
        with obs.trace("outer"):
            pass
    assert obs.installed() is None
    assert [s["name"] for s in t1.tracer.spans] == ["outer"]
    assert [s["name"] for s in t2.tracer.spans] == ["inner"]


def test_null_path_allocates_no_span_records():
    tele = obs.Telemetry()
    with obs.install(tele):
        with obs.trace("on"):
            pass
    # uninstalled again: tracing leaves no trace anywhere
    before = len(tele.tracer.spans)
    for _ in range(100):
        with obs.trace("off"):
            obs.counter_inc("c")
    assert len(tele.tracer.spans) == before == 1
    assert not tele.registry.snapshot()["metrics"]


# ---------------------------------------------------------------------------
# metrics algebra
# ---------------------------------------------------------------------------
def test_histogram_buckets_exact_boundaries():
    from repro.obs.metrics import MIN_EXP, bucket_exp

    assert bucket_exp(0.25) == -2  # exact power of two: its own bucket
    assert bucket_exp(8) == 3
    assert bucket_exp(8.0001) == 4
    assert bucket_exp(9) == 4
    assert bucket_exp(0) == MIN_EXP
    assert bucket_exp(-5) == MIN_EXP


def test_injected_clock_pins_exact_histogram_buckets():
    """A simulated clock drives timer() durations, so the test pins the
    exact bucket each observation lands in — no real-time flakiness."""
    now = [0.0]
    reg = obs.MetricsRegistry(clock=lambda: now[0])
    for dt in (0.25, 0.25, 0.1, 3.0):
        with reg.timer("stage_seconds"):
            now[0] += dt
    snap = reg.snapshot()["metrics"]["stage_seconds"]
    # 0.25 = 2^-2 exactly (twice); 0.1 in (2^-4, 2^-3]; 3.0 in (2, 4]
    assert snap["buckets"] == {"-3": 1, "-2": 2, "2": 1}
    assert snap["count"] == 4 and snap["max"] == 3.0
    assert snap["min"] == pytest.approx(0.1)
    assert reg.histogram("stage_seconds").quantile(0.5) == 0.25


def test_histogram_merge_associative_across_shard_order(rng):
    """Folding per-shard histograms must give one aggregate regardless of
    merge order/grouping — the property that lets shards and benchmark
    subprocesses aggregate without coordination."""
    from repro.obs.metrics import Histogram

    shard_samples = [rng.exponential(0.01, size=50) for _ in range(4)]

    def fold(order, grouping):
        hs = []
        for i in order:
            h = Histogram()
            for v in shard_samples[i]:
                h.observe(float(v))
            hs.append(h)
        if grouping == "left":  # ((0+1)+2)+3
            acc = hs[0]
            for h in hs[1:]:
                acc.merge(h)
        else:  # (0+1) + (2+3)
            hs[0].merge(hs[1])
            hs[2].merge(hs[3])
            hs[0].merge(hs[2])
            acc = hs[0]
        return acc.snapshot()

    ref = fold([0, 1, 2, 3], "left")
    assert fold([3, 1, 0, 2], "left") == ref
    assert fold([2, 0, 3, 1], "pairs") == ref
    assert ref["count"] == 200


def test_registry_merge_counters_gauges_events():
    a, b = obs.MetricsRegistry(), obs.MetricsRegistry()
    a.counter("reqs", engine="search").inc(3)
    b.counter("reqs", engine="search").inc(4)
    b.counter("reqs", engine="live").inc(1)
    a.gauge("epoch").set(1)
    b.gauge("epoch").set(7)  # gauge: last write (the merged-in side) wins
    a.record_event("recovery", replayed=2)
    b.record_event("recovery", replayed=5)
    a.merge(b)
    m = a.snapshot()
    assert m["metrics"]["reqs{engine=search}"]["value"] == 7
    assert m["metrics"]["reqs{engine=live}"]["value"] == 1
    assert m["metrics"]["epoch"]["value"] == 7
    assert [e["replayed"] for e in m["events"]] == [2, 5]


def test_metric_kind_conflict_raises():
    reg = obs.MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def test_prometheus_exposition_parses():
    reg = obs.MetricsRegistry()
    reg.counter("decode_calls_total", plan="fused", format="vbyte").inc(9)
    reg.gauge("delta_docs").set(4)
    reg.histogram("wal_append_seconds", fsync=True).observe(0.25)
    text = reg.to_prometheus()
    parsed = parse_prometheus(text)
    assert parsed['decode_calls_total{format="vbyte",plan="fused"}'] == 9.0
    assert parsed["delta_docs"] == 4.0
    # cumulative le buckets: the 0.25 observation is in le="0.25" exactly
    assert parsed['wal_append_seconds_bucket{fsync="True",le="0.25"}'] == 1.0
    assert parsed['wal_append_seconds_bucket{fsync="True",le="+Inf"}'] == 1.0
    assert parsed['wal_append_seconds_count{fsync="True"}'] == 1.0


def test_chrome_trace_roundtrips_parent_child_nesting(tmp_path):
    now = [0.0]
    tele = obs.Telemetry(clock=lambda: now[0])
    with obs.install(tele):
        with obs.trace("request") as root:
            now[0] += 0.001
            with obs.trace("admission"):
                now[0] += 0.002
            with obs.trace("execute"):
                with obs.trace("decode", format="vbyte"):
                    now[0] += 0.004
            root.event("crash_point", phase="after_rotate")
    p = tmp_path / "trace.json"
    tele.tracer.write_chrome_trace(str(p))
    spans = {e["name"]: e for e in read_chrome_trace(str(p))
             if e["ph"] == "X"}
    assert set(spans) == {"request", "admission", "execute", "decode"}
    req = spans["request"]
    assert spans["admission"]["args"]["parent_id"] == req["args"]["span_id"]
    assert spans["execute"]["args"]["parent_id"] == req["args"]["span_id"]
    assert (spans["decode"]["args"]["parent_id"]
            == spans["execute"]["args"]["span_id"])
    assert spans["decode"]["args"]["format"] == "vbyte"
    # microsecond timeline survives exactly (injected clock)
    assert req["dur"] == pytest.approx(7000.0)
    assert spans["decode"]["dur"] == pytest.approx(4000.0)
    # all spans share one tid = trace id; instant event rode along
    assert len({e["tid"] for e in spans.values()}) == 1
    assert any(e["ph"] == "i" and e["name"] == "crash_point"
               for e in read_chrome_trace(str(p)))


def test_jsonl_roundtrip_and_trees(tmp_path):
    tele = obs.Telemetry()
    with obs.install(tele):
        for _ in range(3):
            with obs.trace("request"):
                with obs.trace("execute"):
                    pass
    p = tmp_path / "trace.jsonl"
    tele.tracer.write_jsonl(str(p))
    recs = read_jsonl(str(p))
    assert len(recs) == 6
    trees = tele.tracer.trees()
    assert len(trees) == 3  # one trace per request
    for tid, spans in trees.items():
        names = {s["name"] for s in spans}
        assert names == {"request", "execute"}
        root = next(s for s in spans if s["parent_id"] is None)
        assert root["span_id"] == tid


def test_span_exception_tags_error_and_unwinds():
    tele = obs.Telemetry()
    with obs.install(tele):
        with pytest.raises(ValueError):
            with obs.trace("request"):
                with obs.trace("execute"):
                    raise ValueError("boom")
        with obs.trace("next"):
            pass
    by_name = {s["name"]: s for s in tele.tracer.spans}
    assert by_name["execute"]["attrs"]["error"] == "ValueError"
    assert by_name["request"]["attrs"]["error"] == "ValueError"
    # the stack unwound: the next root starts a fresh trace
    assert by_name["next"]["parent_id"] is None


# ---------------------------------------------------------------------------
# shared percentile/latency helpers
# ---------------------------------------------------------------------------
def test_percentile_matches_numpy(rng):
    from repro.obs.stats import latency_summary, percentile

    xs = rng.exponential(1.0, size=137).tolist()
    for q in (0, 13.7, 50, 90, 99, 100):
        assert percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)), abs=1e-12)
    with pytest.raises(ValueError):
        percentile([], 50)
    s = latency_summary([0.001, 0.002, 0.004], 0.01, 3)
    assert s["qps"] == 300.0 and s["p50_ms"] == 2.0


# ---------------------------------------------------------------------------
# QueryStats merge-by-fields contract
# ---------------------------------------------------------------------------
def test_querystats_merge_new_field_fails_loudly():
    """Adding a field without a merge rule must raise, not silently drop."""
    import dataclasses

    from repro.index import QueryStats

    @dataclasses.dataclass
    class Extended(QueryStats):
        mystery: object = None

    a, b = Extended(), Extended()
    with pytest.raises(TypeError, match="mystery"):
        a.merge(b)


def test_querystats_merge_covers_every_current_field():
    from repro.index import QueryStats

    a, b = QueryStats(), QueryStats()
    a.blocks_decoded, b.blocks_decoded = 3, 4
    b.degraded = True
    b.degraded_reasons.append("deadline:gallop")
    a.merge(b)
    assert a.blocks_decoded == 7
    assert a.degraded is True
    assert a.degraded_reasons == ["deadline:gallop"]
    a.merge(b)  # list fields dedup on re-merge
    assert a.degraded_reasons == ["deadline:gallop"]


# ---------------------------------------------------------------------------
# end-to-end wiring: query spans, decode attribution, bit-exactness
# ---------------------------------------------------------------------------
def _small_index(rng, n_terms=6, universe=50_000):
    from repro.data.synthetic import posting_tfs
    from repro.index import build_index

    lists = {t: np.sort(rng.choice(universe, size=int(s), replace=False))
             .astype(np.uint32)
             for t, s in enumerate(rng.integers(200, 800, size=n_terms))}
    tfs = {t: posting_tfs(rng, len(v)) for t, v in lists.items()}
    return build_index(lists, tfs=tfs, block_size=32, n_docs=universe)


def test_maxscore_span_tree_sums_to_request_wall_time(rng):
    """ISSUE acceptance: one span tree per maxscore topk whose direct
    children durations sum (within tolerance) to the root wall time, and
    decode spans attributed to (format, plan, epilogue)."""
    from repro.index import topk
    from repro.launch.serve import SearchEngine

    index = _small_index(rng)
    engine = SearchEngine(index, top_k=10)
    terms = [0, 2, 4]
    engine.search(terms, "topk_maxscore")  # compile outside the capture

    tele = obs.Telemetry()
    with obs.install(tele):
        ids, scores = engine.search(terms, "topk_maxscore")
    off_ids, off_scores = engine.search(terms, "topk_maxscore")
    np.testing.assert_array_equal(ids, off_ids)
    np.testing.assert_array_equal(scores, off_scores)

    trees = tele.tracer.trees()
    assert len(trees) == 1  # one trace for the one request
    spans = next(iter(trees.values()))
    root = next(s for s in spans if s["parent_id"] is None)
    assert root["name"] == "request"
    children = [s for s in spans if s["parent_id"] == root["span_id"]]
    assert {c["name"] for c in children} == {"admission", "execute",
                                            "finalize"}
    # the stages partition the request: their durations sum to the root
    # wall time (tolerance: the span-open/close code between stages)
    child_sum = sum(c["dur"] for c in children)
    assert child_sum <= root["dur"] + 1e-9
    assert child_sum >= 0.90 * root["dur"]

    decode_spans = [s for s in spans if s["name"] == "decode"]
    assert decode_spans, "no decode spans under the request tree"
    for d in decode_spans:
        assert d["attrs"]["format"] == index.terms[0].arr.format
        assert isinstance(d["attrs"]["plan"], str) and d["attrs"]["plan"]
        assert "epilogue" in d["attrs"]
        assert d["attrs"]["blocks"] >= 1
    # topk span got the QueryStats attribute dump
    tk = next(s for s in spans if s["name"] == "topk")
    assert tk["attrs"]["mode"] == "maxscore"
    assert tk["attrs"]["blocks_decoded"] >= 1

    # with telemetry uninstalled nothing further records
    engine.search(terms, "topk_maxscore")
    assert len(tele.tracer.trees()) == 1


def test_topk_bit_identical_with_and_without_telemetry(rng):
    from repro.index import topk

    index = _small_index(rng)
    cases = [([0, 1], "or"), ([0, 2, 4], "maxscore"), ([1, 3], "and")]
    base = [topk(index, t, 10, mode=m) for t, m in cases]
    tele = obs.Telemetry()
    with obs.install(tele):
        on = [topk(index, t, 10, mode=m) for t, m in cases]
    after = [topk(index, t, 10, mode=m) for t, m in cases]
    for (bi, bs), (oi, os_), (ai, as_) in zip(base, on, after):
        np.testing.assert_array_equal(bi, oi)
        np.testing.assert_array_equal(bs, os_)
        np.testing.assert_array_equal(bi, ai)
        np.testing.assert_array_equal(bs, as_)
    assert len(tele.tracer.trees()) == len(cases)


def test_serve_counters_mirror_serve_stats(rng):
    """SearchEngine keeps the serve_stats dict API and mirrors increments
    into labeled registry counters."""
    from repro.launch.serve import SearchEngine

    index = _small_index(rng)
    engine = SearchEngine(index, top_k=5)
    tele = obs.Telemetry()
    with obs.install(tele):
        engine.search([0, 1], "or")
        engine.search([2], "topk")
    m = tele.registry.snapshot()["metrics"]
    key = 'serve_requests_total{engine=search,mode=or}'
    assert m[key]["value"] == 1
    assert m['serve_requests_total{engine=search,mode=topk}']["value"] == 1
    assert any(k.startswith("decode_calls_total") for k in m)
    assert any(k.startswith("plan_cache_total") for k in m)


def test_wal_and_recovery_metrics(tmp_path, rng):
    from repro.index.ingest import LiveIndex

    tele = obs.Telemetry()
    with obs.install(tele):
        d = str(tmp_path / "live")
        li = LiveIndex(d, n_docs=1 << 12)
        for doc in range(40):
            li.add(doc, {int(t): 1 for t in rng.choice(8, 2, replace=False)})
        li.merge()
        li.close()
        li = LiveIndex(d)
        li.add(50, {0: 1})
        li.close()
        LiveIndex(d).close()  # replays the unmerged op
    snap = tele.registry.snapshot()
    m = snap["metrics"]
    assert m["wal_append_seconds{fsync=True}"]["count"] == 41
    assert m["wal_record_bytes"]["count"] == 41
    phases = [k for k in m if k.startswith("ingest_merge_phase_seconds")]
    assert len(phases) == 8  # one histogram per crash point
    assert m["ingest_merges_total"]["value"] == 1
    recov = [e for e in snap["events"] if e["event"] == "ingest_recovery"]
    assert len(recov) == 3  # one structured record per reopen
    assert recov[-1]["replayed_ops"] == 1


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------
def test_report_cli_renders_stage_table(tmp_path, capsys):
    from repro.obs import report

    now = [0.0]
    tele = obs.Telemetry(clock=lambda: now[0])
    with obs.install(tele):
        with obs.trace("topk", term=3):
            with obs.trace("decode", term=3, blocks_decoded=4,
                           ints_decoded=512, blocks=[0, 1]):
                now[0] += 0.004
            with obs.trace("score", term=3):
                now[0] += 0.001
    p = tmp_path / "cap.jsonl"
    tele.tracer.write_jsonl(str(p))
    assert report.main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "decode" in out and "p50" in out
    assert "hottest" in out.lower()
    assert report.main([str(tmp_path / "missing.jsonl")]) == 1
