"""Readings of the program's ``obs`` spans for the per-layer metrics. A
span record is ``{"type": "span", "name", "ts", "dur", "span_id",
"parent_id", "trace_id", "attrs"}`` (``repro.obs.trace``)."""
from __future__ import annotations


def spans_named(spans, name: str) -> list[dict]:
    return [s for s in spans if s["type"] == "span" and s["name"] == name]
