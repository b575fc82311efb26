"""The benchmark harness: find a cell by name, run it, print its result.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``BENCHMARK.json`` ``workloads[]`` names the cell's configuration and
  traffic; ``configs[].file`` is the configuration's JSON, whose
  ``driver`` names ``chipbench/drivers/<driver>.py``;
* ``chipbench/traffic/<traffic>.json`` holds the mix's parameters;
* ``chipbench/layer_metrics/<metric>.py`` reads one per-layer metric.

A driver module names ``SPAN_NAMES``, the program's spans an idle gap of
the device may be charged to, and has four functions:

* ``setup(config, traffic, seed)`` makes the data from the seed, builds
  the system and warms up every shape the window uses; returns a state;
* ``window(state, seconds)`` drives the entry point in a closed loop for
  ``seconds`` and returns a :class:`Window`;
* ``end_to_end(state, window)`` returns the end-to-end metrics by name;
* ``check(state, window)`` compares what the window produced with the
  reference and returns ``{name: (value, limit)}``; a run is correct when
  every value is at most its limit;
* optionally ``footprint(state)``: named device bytes to print beside
  ``memory_peak_bytes``, so that what a deployment holds can be told from
  padding and from what only the check keeps.

A layer-metric module has ``read(ctx)``, returning a number or ``None``
when the trace holds nothing for it (the metric is then left out).
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from types import ModuleType

BENCH_DIR = "chipbench"


class Refused(Exception):
    """The run cannot measure (no chip, unknown name, missing program)."""


@dataclass
class Window:
    """What one measured window did."""

    seconds: float  # start to the first completion after --seconds
    attempted: int
    failed: int
    work: dict = field(default_factory=dict)  # driver's counts for readers


@dataclass
class Cell:
    chips: int
    config: dict
    traffic: dict
    driver: ModuleType
    end_to_end: list[dict]
    per_layer: list[dict]


@dataclass
class LayerContext:
    """What a per-layer reader may read."""

    spans: list[dict]  # obs span records of the window
    counters: dict  # obs registry snapshot, "name{labels}" -> value
    trace: object  # tracing.TraceSummary of the window
    window: Window
    peaks: dict  # this device kind's row of peaks.json


def _load_module(path: str, name: str) -> ModuleType:
    if not os.path.isfile(path):
        raise Refused(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise Refused(f"missing file {path}")
    with open(path) as fh:
        return json.load(fh)


def resolve(root: str, workload: str) -> Cell:
    """The cell named ``workload`` and every file it needs, by name."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, BENCH_DIR, "traffic",
                                      f"{w['traffic']}.json"))
    driver = _load_module(
        os.path.join(root, BENCH_DIR, "drivers", f"{config['driver']}.py"),
        f"chipbench_driver_{config['driver']}")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in reported]
    return Cell(chips=int(w["chips"]), config=config,
                traffic=traffic, driver=driver, end_to_end=e2e,
                per_layer=per_layer)


def accelerator(chips: int):
    """The first ``chips`` TPU devices; refuses anything else."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devices[0].platform!r} devices; "
                      "this benchmark measures only on the chip")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    return devices[:chips]


def enable_cache() -> str:
    """The program's persistent compilation cache (``.jax_cache/`` in the
    checkout, or ``$JAX_COMPILATION_CACHE_DIR``), keeping every program,
    however small or quick, so a second run of a cell compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compiles while active, and the seconds of each stage of
    getting a program. JAX reports ``backend_compile_duration`` around
    every executable it gets, a persistent-cache hit included, so a
    compile is a request that did not hit the cache."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    STAGES = {"trace": "/jax/core/compile/jaxpr_trace_duration",
              "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "compile or load": REQUEST}

    def __init__(self):
        import jax

        self.active = False
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def reset(self):
        self.requests = self.hits = 0
        self.seconds = dict.fromkeys(self.STAGES, 0.0)

    @property
    def compiles(self) -> int:
        return self.requests - self.hits

    def _on_duration(self, event, duration, **kw):
        if not self.active:
            return
        self.requests += event == self.REQUEST
        for stage, name in self.STAGES.items():
            if event == name:
                self.seconds[stage] += duration

    def _on_event(self, event, **kw):
        if self.active and event == self.HIT:
            self.hits += 1

    def __str__(self):
        stages = ", ".join(f"{k} {v:.1f} s" for k, v in self.seconds.items())
        return (f"{self.compiles} compiles, {self.hits} programs from the "
                f"persistent cache ({stages})")


def _device_kind_peaks(root: str, kind: str) -> dict:
    table = _load_json(os.path.join(root, BENCH_DIR, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json; add its "
                       "published peaks with their source")
    return table["devices"][kind]


def run(root: str, workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, devices=None, log=None, patch=None) -> dict:
    """Run one cell; return the result object (the last stdout line).

    ``devices`` skips the look for a chip (tests pass the CPU device);
    ``patch(state)`` may replace the state's ``entry`` after set-up, which
    is how a control or a planted fault takes the timed path's place.
    """
    import jax

    from repro import obs

    from chipbench import tracing

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = resolve(root, workload)
    if devices is None:
        devices = accelerator(cell.chips)
    log(f"cache: {enable_cache()}")
    counter = CompileCounter()
    counter.active = True
    state = cell.driver.setup(cell.config, cell.traffic, seed, devices=devices,
                              log=log)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f}: {counter} (0 compiles once the cache "
        "holds every program)")
    if patch is not None:
        patch(state)
    counter.reset()

    tele = trace_dir = None
    if trace:
        tele = obs.Telemetry(jax_annotations=True)
        trace_dir = os.path.join(root, ".bench_trace", workload, str(seed))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        obs.install(tele)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            win = cell.driver.window(state, seconds)
    finally:
        counter.active = False
        if trace:
            obs.uninstall()
            jax.profiler.stop_trace()
    log(f"window {win.seconds:.3f} s, {win.attempted} attempted, "
        f"{win.failed} failed, {counter.compiles} compiles in the window")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result: dict = {"correct": False, "attempted": win.attempted,
                    "failed": win.failed, "metrics": {}, "device": device}
    if trace:
        t0 = time.perf_counter()
        summary = tracing.summarize(
            tracing.read_xspace(tracing.find_xplane(trace_dir)),
            set(cell.driver.SPAN_NAMES) | {tracing.WINDOW})
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = LayerContext(spans=list(tele.tracer.spans),
                           counters=tele.registry.snapshot()["metrics"],
                           trace=summary, window=win,
                           peaks=_device_kind_peaks(root, device["kind"])
                           if device["platform"] == "tpu" else {})
        for m in cell.per_layer:
            reader = _load_module(
                os.path.join(root, BENCH_DIR, "layer_metrics",
                             f"{m['name']}.py"),
                f"chipbench_metric_{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        result["breakdown"] = summary.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t0:.1f} s")
    else:
        values = cell.driver.end_to_end(state, win)
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise RuntimeError(f"driver {cell.config['driver']!r} gives "
                                   f"no end-to-end metric {m['name']!r}")
            result["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                            "unit": m["unit"]}

    checks = cell.driver.check(state, win)
    result["correct"] = bool(checks) and win.attempted > 0 and win.failed == 0 \
        and all(v <= lim for v, lim in checks.values())
    result["compiles_in_window"] = counter.compiles
    if hasattr(cell.driver, "footprint"):
        result["footprint_bytes"] = cell.driver.footprint(state)
        log(f"device bytes beside the peak: {result['footprint_bytes']}")
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} {v} limit {lim}")
    return result
