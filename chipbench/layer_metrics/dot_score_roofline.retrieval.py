"""Scoring-kernel layer: the fused ``dot_score`` kernel's share of its
roofline, in %.

The least time the chip could take for the window's calls is their bytes
of work (``work_retrieval.bytes_of_work``: tight payload, per-block
metadata, each candidate's table row, id and scores) over the HBM peak;
the scoring is 2·d·b flops a candidate, far under the MXU's peak for the
bytes it needs, so bandwidth bounds it. The share is that least time over
the device time of the kernel, matched by its stable name. Where no such
kernel ran (a program without it), the metric is left out."""
from chipbench import work_retrieval


def read(ctx):
    kernel_s = ctx.trace.device_seconds(work_retrieval.DOT_SCORE_KERNEL)
    if kernel_s <= 0 or "bytes_of_work" not in ctx.window.work:
        return None
    least_s = ctx.window.work["bytes_of_work"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
