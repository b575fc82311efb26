"""Device layer, in the retrieval cell: 1 - (union of device op
intervals) / traced window, in %, averaged over the chips
(``tracing.summarize``): how far the host holds the chip back between
``retrieve`` calls."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
