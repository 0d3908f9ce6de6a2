"""Time inside the benchmark's ``bench.execute`` spans in which no
chip ran an operation, per request: prepare, bind, plan-cache lookup,
dispatch, overflow check and the fetch (device trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 1e3 * ctx.trace["serve_host_s"] / ctx.window.attempted
