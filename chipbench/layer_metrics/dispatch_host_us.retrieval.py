"""Dispatch layer, in the retrieval cell: mean host duration of the
``decode`` span per ``dispatch.decode`` call, in us. Each
``ServingEngine.retrieve`` makes one such call (its ``retrieve.score``
child), the fused ``dot_score`` decode; it returns before the device ends,
so this is the host's share of the call. Left out where the program has no
such span."""
from chipbench.spans import spans_named


def read(ctx):
    spans = spans_named(ctx.spans, "decode")
    if not spans:
        return None
    return sum(s["dur"] for s in spans) / len(spans) * 1e6
