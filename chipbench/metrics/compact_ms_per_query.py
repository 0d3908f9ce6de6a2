"""Device time of the operations under the program's
``rows_from_mask`` named scope (compaction of a mask into row slots:
a prefix count and a binary search), the union of a chip's such
operations, mean over chips, per request (device trace)."""
from chipbench import stages


def read(ctx):
    return stages.scope_ms(ctx, "rows_from_mask")
