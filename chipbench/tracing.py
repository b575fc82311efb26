"""Reduce a JAX profiler trace to per-layer numbers.

The run records one trace of its measured window with the program's
``obs`` spans mirrored into the profiler (``Telemetry(jax_annotations=
True)``), so host spans and device operations share the profiler's clock.
The harness opens one more annotation, :data:`WINDOW`, around the window
itself; its interval is the traced window.

From the trace this module takes:

* the device operations of each chip (the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane), clipped to the window;
* busy time: the union of those intervals, per chip, averaged over the
  chips; the idle share is one minus busy over the window;
* device time by operation name, so a kernel's time is the sum over the
  operations whose name matches it;
* idle gaps, each charged to the innermost host annotation from a given
  set (the ``obs`` span names) that was open at the gap's midpoint.

:func:`read_xspace` turns the ``.xplane.pb`` file into plain tuples and
:func:`summarize` works on those alone, so the arithmetic can be checked
on hand-made events.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

WINDOW = "bench.window"
NO_SPAN = "(no span open)"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_ENQUEUE = "DoEnqueueProgram"  # the host's hand-off of one program
# every Mosaic kernel of the program is a VByte decode-tile core (plain or
# with a fused epilogue); the profiler names an op by its HLO text
DECODE_KERNELS = r'custom_call_target="tpu_custom_call"'


@dataclass
class RawTrace:
    """Events as ``(name, start_ns, duration_ns)``, device ops per chip and
    host events per host thread, on the host's clock."""

    device: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)
    host: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)
    skew_ns: float = 0.0  # added to the device's times (see device_skew)


def device_skew(enqueue_ends, module_starts) -> float:
    """Nanoseconds to add to device times to put them on the host's clock.

    The profiler's device timestamps can run behind the host's by about a
    millisecond on a v5e. A program cannot start on the device before the
    host has enqueued it, so by the time the k-th program starts at least k
    enqueues have ended: ``E[k] <= D[k] + skew`` for the sorted enqueue
    ends ``E`` and program starts ``D``. The smallest skew that keeps every
    program after its enqueue is the largest ``E[k] - D[k]``; it errs by
    the launch latency of the quickest launch, tens of microseconds.
    """
    e, d = np.sort(enqueue_ends), np.sort(module_starts)
    n = min(e.size, d.size)
    return float(np.max(e[:n] - d[:n])) if n else 0.0


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(files)}")
    return files[0]


def read_xspace(path: str) -> RawTrace:
    """Device ops of every TPU plane and the events of every host line."""
    from jax.profiler import ProfileData

    with open(path, "rb") as fh:
        data = ProfileData.from_serialized_xspace(fh.read())
    raw = RawTrace()
    modules = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    raw.device[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
                elif line.name == _MODULES_LINE:
                    modules += [e.start_ns for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
                if evs:
                    raw.host[f"{plane.name}/{line.name}"] = evs
    enqueues = [s + d for evs in raw.host.values()
                for name, s, d in evs if name == _ENQUEUE]
    raw.skew_ns = device_skew(np.array(enqueues, np.float64),
                              np.array(modules, np.float64))
    raw.device = {p: [(n, s + raw.skew_ns, d) for n, s, d in evs]
                  for p, evs in raw.device.items()}
    return raw


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merged intervals of ``[starts, ends)``, sorted."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


class _SpanIndex:
    """Innermost open annotation at a time, over properly nested events of
    one host thread (a stack sweep gives each event its parent)."""

    def __init__(self, events):
        events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
        self.names = [ev[0] for ev in events]
        self.starts = np.array([ev[1] for ev in events], np.float64)
        self.ends = np.array([ev[1] + ev[2] for ev in events], np.float64)
        self.parent = np.full(len(events), -1, np.int64)
        stack: list[int] = []
        for i in range(len(events)):
            while stack and self.ends[stack[-1]] <= self.starts[i]:
                stack.pop()
            self.parent[i] = stack[-1] if stack else -1
            stack.append(i)

    def innermost(self, t: float):
        """``(start, name)`` of the innermost event covering ``t``."""
        i = int(np.searchsorted(self.starts, t, side="right")) - 1
        while i >= 0 and self.ends[i] <= t:
            i = int(self.parent[i])
        return (self.starts[i], self.names[i]) if i >= 0 else None


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over the chips
    n_chips: int
    op_seconds: dict[str, float]  # device time by op name, all chips
    idle_by_span: dict[str, float]  # idle seconds by host span, per chip

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_seconds(self, pattern: str) -> float:
        """Device time of every op whose name matches ``pattern`` (a regular
        expression, searched), summed over the chips."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_seconds.items() if rx.search(name))

    def breakdown(self, top: int = 10) -> dict:
        by_op: dict[str, float] = defaultdict(float)
        for name, secs in self.op_seconds.items():
            by_op[short_op_name(name)] += secs
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, float(s)] for n, s in ops],
                "idle_gaps": [[n, float(s)] for n, s in gaps]}


def short_op_name(name: str) -> str:
    """``%vbyte_decode_blocked.1 = s32[..] custom-call(..), ..`` ->
    ``vbyte_decode_blocked [tpu_custom_call]``: the op's name without its
    instance number, and the kernel mark for Mosaic kernels."""
    op = re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))
    return f"{op} [tpu_custom_call]" if re.search(DECODE_KERNELS, name) \
        else op


def window_bounds(raw: RawTrace) -> tuple[float, float]:
    """Start and end (ns) of the one :data:`WINDOW` annotation."""
    found = [(s, s + d) for evs in raw.host.values()
             for name, s, d in evs if name == WINDOW]
    if len(found) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} annotation in the "
                           f"trace, found {len(found)}")
    return found[0]


def summarize(raw: RawTrace, span_names, *, bounds=None) -> TraceSummary:
    """Reduce a trace over its window (``bounds`` in ns, else the
    :data:`WINDOW` annotation). ``span_names`` are the host annotations a
    gap may be charged to."""
    t0, t1 = bounds if bounds is not None else window_bounds(raw)
    if not raw.device:
        raise RuntimeError("the trace holds no TPU device plane")
    span_names = set(span_names)
    indexes = [_SpanIndex([ev for ev in evs if ev[0] in span_names])
               for evs in raw.host.values()]
    indexes = [ix for ix in indexes if ix.names]
    op_seconds: dict[str, float] = defaultdict(float)
    idle_by_span: dict[str, float] = defaultdict(float)
    busy_total = 0.0
    for evs in raw.device.values():
        st = np.array([e[1] for e in evs], np.float64)
        en = st + np.array([e[2] for e in evs], np.float64)
        cs, ce = np.clip(st, t0, t1), np.clip(en, t0, t1)
        for (name, _, _), a, b in zip(evs, cs, ce):
            if b > a:
                op_seconds[name] += (b - a) * 1e-9
        us, ue = _union(cs[ce > cs], ce[ce > cs])
        busy_total += float((ue - us).sum()) * 1e-9
        gap_s = np.concatenate([[t0], ue])
        gap_e = np.concatenate([us, [t1]])
        for a, b in zip(gap_s, gap_e):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            hits = [h for h in (ix.innermost(mid) for ix in indexes) if h]
            name = max(hits)[1] if hits else NO_SPAN
            idle_by_span[name] += (b - a) * 1e-9
    n = len(raw.device)
    return TraceSummary(window_s=(t1 - t0) * 1e-9, busy_s=busy_total / n,
                        n_chips=n, op_seconds=dict(op_seconds),
                        idle_by_span={k: v / n for k, v in
                                      idle_by_span.items()})
