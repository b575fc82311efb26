"""Decode-kernel layer: the VByte decode kernels' share of their roofline, %.

The least time the chip could take for the window's decodes is their bytes
of work (``work.bytes_of_work``: tight payload, per-block metadata, 4 B per
real int out) over the HBM peak; decoding needs a few integer operations
per byte, far under any compute peak, so bandwidth bounds it. The share is
that least time over the kernels' device time in the trace. The kernels
read the padded grid and write padded blocks, more than these bytes, so
the share cannot pass 100 %."""
from chipbench import tracing


def read(ctx):
    kernel_s = ctx.trace.device_seconds(tracing.DECODE_KERNELS)
    if kernel_s <= 0:
        return None
    least_s = ctx.window.work["bytes_of_work"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
