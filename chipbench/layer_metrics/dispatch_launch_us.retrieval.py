"""Dispatch layer, in the retrieval cell: mean host duration of the
``decode.launch`` span per ``dispatch.decode`` call, in us: the call into
the jitted fused ``dot_score`` program, up to its return of the output
before the device has produced it. Left out where the program has no such
span."""
from chipbench.spans import spans_named


def read(ctx):
    spans = spans_named(ctx.spans, "decode.launch")
    if not spans:
        return None
    return sum(s["dur"] for s in spans) / len(spans) * 1e6
