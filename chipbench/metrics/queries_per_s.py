"""Requests completed in the window over the window's seconds, first
request sent to last rows decoded (host clock)."""
from chipbench import stats


def read(ctx):
    w = ctx.window
    return stats.rate(w.attempted - w.failed, w.seconds)
