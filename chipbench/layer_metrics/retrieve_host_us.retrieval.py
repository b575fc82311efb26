"""Serving-engine layer: mean host duration of the ``retrieve`` span per
``ServingEngine.retrieve`` call, in us: the user tower's launch, the fused
scoring's dispatch and the top-k's launch. The call returns before the
device has finished, so this is the host's share of a call. Left out where
the program has no such span."""
from chipbench.spans import spans_named


def read(ctx):
    spans = spans_named(ctx.spans, "retrieve")
    if not spans:
        return None
    return sum(s["dur"] for s in spans) / len(spans) * 1e6
