"""Dispatch layer, in the retrieval cell: mean host duration of the
``decode.prepare`` span per ``dispatch.decode`` call, in us: unwrapping
the corpus's operands, epilogue and operand checks, plan resolution and
mesh detection, before the launch. Left out where the program has no such
span."""
from chipbench.spans import spans_named


def read(ctx):
    spans = spans_named(ctx.spans, "decode.prepare")
    if not spans:
        return None
    return sum(s["dur"] for s in spans) / len(spans) * 1e6
