"""Union of the device's operation intervals in the traced window,
mean over chips, per request (device trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 1e3 * ctx.trace["busy_s"] / ctx.window.attempted
