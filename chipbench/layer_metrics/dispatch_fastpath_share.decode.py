"""Dispatch layer: share of the window's ``dispatch.decode`` calls that
took the launch cache's hit path, in %: hit / (hit + miss + bypass) of the
program's ``decode_fastpath_total`` counter. Silent on a program that
counts no such thing."""

COUNTER = "decode_fastpath_total"


def read(ctx):
    by_result = {}
    for key, snap in ctx.counters.items():
        name, _, labels = key.partition("{")
        if name != COUNTER:
            continue
        fields = dict(kv.split("=", 1) for kv in labels.rstrip("}").split(",")
                      if "=" in kv)
        result = fields.get("result")
        by_result[result] = by_result.get(result, 0) + snap["value"]
    total = sum(by_result.values())
    if not total:
        return None
    return 100.0 * by_result.get("hit", 0) / total
