"""``QueryService.warmup`` of every plan the mix uses, and one request
of each at its largest binding (host clock)."""


def read(ctx):
    return ctx.setup["warmup_s"]
