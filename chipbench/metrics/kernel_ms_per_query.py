"""Summed device time of the Pallas kernels' events in the traced
window, mean over chips, per request (device trace).

KERNELS names the kernels' operations as the trace shows them, read by
hand from a trace of the analytic cell on a TPU v5 lite: the program
gives its ``pallas_call``s no name, so each shows as a ``%vmap__.<n>``
custom call whose target is ``tpu_custom_call``, the only such target
on the query path.
"""
import fnmatch

KERNELS = ('*custom_call_target="tpu_custom_call"*',)


def seconds(ctx) -> float:
    return sum(v for k, v in ctx.trace["op_s"].items()
               if any(fnmatch.fnmatchcase(k, p) for p in KERNELS))


def read(ctx):
    if ctx.trace is None:
        return None
    s = seconds(ctx)
    return 1e3 * s / ctx.window.attempted if s > 0 else None
