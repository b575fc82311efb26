"""Dispatch layer: mean host duration of the ``decode`` span per
``dispatch.decode`` call, in us. The call returns before the device ends,
so this is the host's share of a call: plan, operand checks, launch."""
from chipbench.spans import spans_named


def read(ctx):
    spans = spans_named(ctx.spans, "decode")
    if not spans:
        return None
    return sum(s["dur"] for s in spans) / len(spans) * 1e6
