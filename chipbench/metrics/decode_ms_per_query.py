"""Host decode of the rows, ``ResultSet.rows()``, per request (the
benchmark's host clock around the call)."""


def read(ctx):
    return 1e3 * sum(ctx.window.decode_s) / ctx.window.attempted
