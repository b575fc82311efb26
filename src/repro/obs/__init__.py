"""Unified telemetry: metrics registry, per-request trace spans, exporters.

Dependency-free observability layer for the serving / query / dispatch /
ingestion stack (docs/observability.md). Three pieces:

* :class:`~repro.obs.metrics.MetricsRegistry` — counters, gauges and
  log-bucketed histograms. Mergeable (``reg.merge(other)`` folds a logical
  shard's or a subprocess sweep's registry in associatively) and
  clock-injectable (``MetricsRegistry(clock=...)``) so tests pin exact
  timelines.
* :class:`~repro.obs.trace.Span` / :func:`~repro.obs.trace.trace` —
  context-manager tracing. Nested spans form one tree per request
  (admission → validate → plan-resolve → decode dispatch →
  kernel/epilogue → skip-gallop/merge → score → top-k), each carrying
  structured attributes (format, plan label, chunk width, blocks
  decoded/skipped/pruned, epilogue name).
* exporters (:mod:`repro.obs.exporters`) — JSONL event log,
  Prometheus-style text exposition, Chrome-trace/Perfetto JSON — plus the
  ``python -m repro.obs.report`` CLI over a JSONL capture.

**The clean fast path stays bit-exact and cheap.** Nothing is recorded by
default: every instrumentation site goes through the module-level null
recorder (one global read + ``None`` check, no span objects allocated).
Telemetry activates only under :func:`install`::

    from repro import obs

    tele = obs.Telemetry()          # registry + tracer
    with obs.install(tele):         # or obs.install(tele); ... obs.uninstall()
        engine.run_workload(qs)
    print(tele.registry.to_prometheus())
    tele.tracer.write_chrome_trace("trace.json")
"""
from .metrics import Counter, Gauge, Histogram, MetricsRegistry  # noqa: F401
from .stats import latency_summary, percentile  # noqa: F401
from .trace import (  # noqa: F401
    NULL_SPAN,
    Span,
    Telemetry,
    Tracer,
    counter_inc,
    histogram_observe,
    install,
    installed,
    trace,
    uninstall,
)
