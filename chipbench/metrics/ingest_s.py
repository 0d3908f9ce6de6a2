"""Records from the seed and the program's bulk shredder building the
database (host clock)."""


def read(ctx):
    return ctx.setup["ingest_s"]
