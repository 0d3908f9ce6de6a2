"""The program's ``fetch`` spans, per request: the device-to-host copy
of the output tiles, after the device has finished (the program's
tracer, mirrored into the device trace)."""
from chipbench import stages


def read(ctx):
    return stages.span_ms(ctx, "fetch")
