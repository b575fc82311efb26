"""Device layer: 1 - (union of device op intervals) / traced window, in %,
averaged over the chips (``tracing.summarize``)."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
