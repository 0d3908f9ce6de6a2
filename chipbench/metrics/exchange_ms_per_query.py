"""Device time of the operations under the program's ``exchange``
named scope (every collective: all_gather, psum, and pmin/pmax built
on all_gather), the union of a chip's such operations, mean over
chips, per request (device trace)."""
from chipbench import stages


def read(ctx):
    return stages.scope_ms(ctx, "exchange")
