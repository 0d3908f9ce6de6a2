"""The program's ``prepare`` spans, per request: parse, rewrite, lift
and verify of texts that missed the prepare memo (the program's
tracer, mirrored into the device trace)."""
from chipbench import stages


def read(ctx):
    return stages.span_ms(ctx, "prepare")
