"""Per-request trace spans + the module-level telemetry switch.

A request produces one *span tree*: a root span (``request``) whose
descendants are the pipeline stages (admission → validate → plan-resolve →
decode dispatch → kernel/epilogue → skip-gallop/merge → score → top-k).
Spans carry structured attributes — format, plan label, chunk width, blocks
decoded/skipped/pruned, epilogue name — set at open time or via
``span.set(...)`` as counts become known.

**Null fast path.** The hot decode/serving code calls :func:`trace` and the
``counter_inc``/``histogram_observe`` helpers unconditionally. With
nothing installed these cost one module-global read and a ``None`` check;
:func:`trace` returns the shared :data:`NULL_SPAN` singleton, so the
clean path allocates no span objects and stays bit-exact. Everything
activates only under :func:`install`, which flips the single module global::

    tele = Telemetry()
    with install(tele):
        engine.search(...)
    tele.tracer.write_chrome_trace("trace.json")

Spans can optionally mirror into ``jax.profiler.TraceAnnotation`` so the
same stage names show up inside an XLA profile
(``Telemetry(jax_annotations=True)``).
"""
from __future__ import annotations

import itertools
import threading
import time

from .metrics import MetricsRegistry


class _NullSpan:
    """Shared no-op recorder: every method returns cheaply, ``set``/``event``
    drop their arguments, and re-entering the same singleton is safe."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def event(self, name, **attrs):
        return self

    def __bool__(self):  # `if span:` guards expensive attribute computation
        return False


NULL_SPAN = _NullSpan()


class Span:
    """One timed stage. Context manager; closing records the span into the
    tracer and pops it off the thread's stack."""

    __slots__ = ("tracer", "name", "attrs", "span_id", "parent_id",
                 "trace_id", "t0", "dur", "_jax_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = 0
        self.parent_id = None
        self.trace_id = 0
        self.t0 = 0.0
        self.dur = 0.0
        self._jax_ann = None

    def set(self, **attrs):
        self.attrs.update(attrs)
        return self

    def event(self, name: str, **attrs):
        """Zero-duration marker inside this span (e.g. a crash point hit)."""
        self.tracer._record_event(self, name, attrs)
        return self

    def __bool__(self):
        return True

    def __enter__(self):
        # open/close are inlined here (not Tracer methods): spans are the
        # instrumented hot path and every avoided call shows up in the
        # serving overhead gate
        tr = self.tracer
        st = tr._stack()
        self.span_id = next(tr._ids)
        if st:
            top = st[-1]
            self.parent_id = top.span_id
            self.trace_id = top.trace_id
        else:
            self.parent_id = None
            self.trace_id = self.span_id  # root: trace keyed by its own id
        st.append(self)
        if tr.jax_annotations:
            try:
                import jax

                self._jax_ann = jax.profiler.TraceAnnotation(self.name)
                self._jax_ann.__enter__()
            except Exception:
                self._jax_ann = None
        self.t0 = tr.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        tr = self.tracer
        self.dur = tr.clock() - self.t0
        if self._jax_ann is not None:
            self._jax_ann.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        st = tr._stack()
        if st and st[-1] is self:
            st.pop()
        elif self in st:  # unwound out of order (exception paths): drop tail
            del st[st.index(self):]
        # list.append is atomic under the GIL; readers take the lock and
        # only ever see a consistent prefix, so the close path is lock-free
        tr.spans.append(
            {"type": "span", "name": self.name, "ts": self.t0,
             "dur": self.dur, "span_id": self.span_id,
             "parent_id": self.parent_id, "trace_id": self.trace_id,
             "attrs": self.attrs})
        return False


class Tracer:
    """Collects finished spans as plain dict records (JSON-ready).

    Parentage comes from a thread-local open-span stack: a span opened while
    another is open on the same thread becomes its child; a span opened on
    an empty stack roots a new trace (one per request). Finished-span
    records append under a lock, so concurrent request threads can share
    one tracer.
    """

    def __init__(self, *, clock=None, jax_annotations: bool = False):
        self.clock = clock or time.perf_counter
        self.jax_annotations = jax_annotations
        self.spans: list[dict] = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        # itertools.count: thread-safe id allocation without taking a lock
        # on the span-open hot path
        self._ids = itertools.count(1)

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    # -- span lifecycle ------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _record_event(self, span: Span, name: str, attrs: dict):
        self.spans.append(
            {"type": "event", "name": name, "ts": self.clock(),
             "span_id": span.span_id, "trace_id": span.trace_id,
             "attrs": attrs})

    # -- queries -------------------------------------------------------------
    def durations(self, name: str) -> list[float]:
        """Durations (seconds) of every finished span with this name."""
        with self._lock:
            return [s["dur"] for s in self.spans
                    if s["type"] == "span" and s["name"] == name]

    def trees(self) -> dict[int, list[dict]]:
        """Finished spans grouped per trace (one entry per request)."""
        out: dict[int, list[dict]] = {}
        with self._lock:
            for s in self.spans:
                if s["type"] == "span":
                    out.setdefault(s["trace_id"], []).append(s)
        return out

    # -- export --------------------------------------------------------------
    def write_jsonl(self, path):
        from .exporters import write_jsonl

        write_jsonl(self, path)

    def write_chrome_trace(self, path):
        from .exporters import write_chrome_trace

        write_chrome_trace(self, path)


class Telemetry:
    """Registry + tracer bundle sharing one clock — the unit of install."""

    def __init__(self, *, clock=None, jax_annotations: bool = False):
        self.registry = MetricsRegistry(clock=clock)
        self.tracer = Tracer(clock=clock, jax_annotations=jax_annotations)


# ---------------------------------------------------------------------------
# the module-level switch: one global, read on every instrumentation site
# ---------------------------------------------------------------------------
_ACTIVE: Telemetry | None = None


class _Installed:
    """Handle returned by :func:`install`: usable as a context manager that
    restores whatever was installed before (supports nesting in tests)."""

    __slots__ = ("_prev",)

    def __init__(self, prev):
        self._prev = prev

    def __enter__(self):
        return _ACTIVE

    def __exit__(self, *exc):
        global _ACTIVE
        _ACTIVE = self._prev
        return False


def install(tele: Telemetry) -> _Installed:
    """Activate telemetry. Plain-call (`install(t)` … `uninstall()`) or
    ``with install(t):`` both work; the ``with`` form restores the previous
    telemetry on exit."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tele
    return _Installed(prev)


def uninstall():
    global _ACTIVE
    _ACTIVE = None


def installed() -> Telemetry | None:
    return _ACTIVE


def trace(name: str, **attrs):
    """Open a stage span — or return :data:`NULL_SPAN` when telemetry is off.

    The off path is the contract: no allocation, no branching beyond one
    global read, identical control flow for the instrumented code.
    """
    t = _ACTIVE
    if t is None:
        return NULL_SPAN
    return Span(t.tracer, name, attrs)


def counter_inc(name: str, n=1, **labels):
    t = _ACTIVE
    if t is not None:
        t.registry.counter(name, **labels).inc(n)


def histogram_observe(name: str, v, **labels):
    t = _ACTIVE
    if t is not None:
        t.registry.histogram(name, **labels).observe(v)
