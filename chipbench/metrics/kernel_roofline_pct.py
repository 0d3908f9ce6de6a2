"""Least time of the window's kernel work over the kernels' device
time (device trace). The least time is the logical bytes of every
join probe, grouping and top-k of the window's requests (work.py) over
the chip's peak HBM bandwidth (peaks.json); bandwidth, not arithmetic,
bounds these operators. On several chips each chip does its share of
the work in the kernel time of that chip, so the bytes are divided by
the chips."""
from chipbench import spec


def read(ctx):
    if ctx.trace is None or not ctx.work_bytes:
        return None
    s = spec.metric("kernel_ms_per_query").seconds(ctx)
    if s <= 0:
        return None
    least = ctx.work_bytes / ctx.cell["chips"] / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / s
