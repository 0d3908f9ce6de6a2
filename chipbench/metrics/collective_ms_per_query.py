"""Device time of the collectives in the traced window, mean over
chips, per request (device trace).

An operation counts when its HLO opcode is a collective: all-gather,
all-reduce, all-to-all, collective-permute or reduce-scatter, or the
``-start`` / ``-done`` half of one. The trace names an operation by its
instruction's HLO text, whose instruction name comes from the program
(``%psum.7 = s32[2048]{0:T(1024)S(1)} all-reduce(%bitcast.4), ...``), so
the opcode is read from the text: the first word in lower case that
opens a parenthesis after `` = ``. A layout's ``T(8,128)`` or ``S(1)``
is in upper case. An operation that encloses a collective, such as a
``while`` loop, is not one itself, so a collective counts once.

On a TPU v5 lite the cell's collectives run as plain ``all-gather`` and
``all-reduce`` operations (some of the program's ``all_gather``s, Q8's
repartition among them, are lowered to an ``all-reduce``); the trace's
``async-start`` / ``async-done`` events are the compiler's asynchronous
slices, not collectives.
"""
import re

COLLECTIVES = frozenset(
    op + half
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter")
    for half in ("", "-start", "-done"))
_OPCODE = re.compile(r" = .*?(?<![\w.-])([a-z][a-z0-9-]*)\(")


def opcode(op: str):
    """The HLO opcode of an operation named by its instruction text."""
    m = _OPCODE.search(op)
    return m.group(1) if m else None


def seconds(ctx) -> float:
    return sum(v for k, v in ctx.trace["op_s"].items()
               if opcode(k) in COLLECTIVES)


def read(ctx):
    if ctx.trace is None:
        return None
    s = seconds(ctx)
    return 1e3 * s / ctx.window.attempted if s > 0 else None
