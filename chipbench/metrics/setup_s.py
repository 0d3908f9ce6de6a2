"""Process start to the first request of the window: imports, data
from the seed, ingest, device load and warm-up (host clock)."""


def read(ctx):
    return ctx.setup["setup_s"]
