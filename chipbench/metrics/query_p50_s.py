"""Median latency of every request of the window, from sending its
text to its rows decoded (host clock)."""
from chipbench import stats


def read(ctx):
    return stats.percentile(ctx.window.latency_s, 50)
