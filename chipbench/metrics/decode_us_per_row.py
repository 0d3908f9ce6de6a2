"""The program's ``decode`` spans (``ResultSet.rows()``) over the rows
they produced, the window's ``rows_decoded`` counter: host time per
decoded row."""
from chipbench import stages


def read(ctx):
    prog = getattr(ctx, "program", None)
    rows = stages.counter(ctx, "rows_decoded")
    if prog is None or not rows:
        return None
    return 1e6 * prog["span_s"].get("decode", 0.0) / rows
