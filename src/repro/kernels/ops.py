"""Public wrappers around the Pallas kernels: the executor's entry points.

On CPU the join kernel executes with ``interpret=True`` — the kernel
body runs as traced JAX ops, validating the exact code that compiles
for TPU. On a real TPU backend interpret switches off.

The *segment engine* entry points (``segmented_aggregate``,
``segment_topk``) are three-way instead: on TPU they run the Pallas
kernel; on CPU they run the kernel's jnp twin from ``kernels.ref``
(bitwise equal on counts, extrema and integer-valued sums, and fast —
the twin is scatter-free, so XLA CPU never serializes it into while
loops); with
``REPRO_KERNEL_INTERPRET=1`` they force the Pallas interpreter, which
is how CI validates the TPU kernel code on CPU
(``scripts/ci.sh --kernels``). ``REPRO_FORCE_JNP=1`` forces the jnp
twin everywhere — the escape hatch documented in README.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import hash_join as _hj
from repro.kernels import ref as _ref
from repro.kernels import seg_aggregate as _seg
from repro.kernels import seg_topk as _stk


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _seg_impl() -> str:
    """'pallas' | 'interpret' | 'jnp' for the segment engine (module
    docstring). Read at trace time: compiled plans bake the choice."""
    if os.environ.get("REPRO_FORCE_JNP") == "1":
        return "jnp"
    if os.environ.get("REPRO_KERNEL_INTERPRET") == "1":
        return "interpret"
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _block_divisor(n: int, target: int = 512) -> int:
    """Largest divisor of n that is <= target (grid-friendly block)."""
    for c in range(min(target, n), 0, -1):
        if n % c == 0:
            return c
    return n


# The dense one-hot engine costs O(N*S); past this many segments the
# O(N) scatter fallback wins on CPU (kernels benchmark sweep under the
# vmap partition simulation — the context every query runs in). The
# serving path's statistics-presized group caps sit well below it.
SEG_DENSE_NSEG_MAX = 32


def hash_join_probe(build_keys, build_valid, probe_keys, probe_valid,
                    bucket: int = 4):
    """Executor adapter: same signature as executor.hash_join_probe.
    The blocked kernel is exact (no hashing), so bucket/overflow are
    moot; overflow is always False."""
    pos, matched = _hj.block_join_probe(
        tuple(build_keys), build_valid, tuple(probe_keys), probe_valid,
        interpret=_interpret())
    return pos, matched, jnp.zeros((), jnp.bool_)


def segmented_aggregate(values, ok, segments, valid, num_segments):
    """Fused segment aggregation (executor group-by entry point).
    values/ok: [N, C] (C >= 0 value columns); segments/valid: [N].
    Returns (counts [S], sums [S, C], mins [S, C], maxs [S, C]); with
    C == 0 the column outputs are empty and only counts are computed.
    Reads the ExecConfig-resolved caps through ``num_segments`` — the
    same capacity the jnp path sizes its segment space with."""
    n, nc = values.shape
    if nc == 0:   # count-only aggregation still needs the one-hot pass
        values = jnp.zeros((n, 1), jnp.float32)
        ok = jnp.zeros((n, 1), jnp.bool_)
        c, s, mn, mx = segmented_aggregate(values, ok, segments, valid,
                                           num_segments)
        return c, s[:, :0], mn[:, :0], mx[:, :0]
    impl = _seg_impl()
    if impl == "jnp":
        if num_segments > SEG_DENSE_NSEG_MAX:
            return _ref.segmented_aggregate_scatter(
                values, ok, segments, valid, num_segments)
        return _ref.segmented_aggregate(values, ok, segments, valid,
                                        num_segments,
                                        block_n=_block_divisor(n))
    return _seg.segmented_aggregate(values, ok, segments, valid,
                                    num_segments,
                                    interpret=(impl == "interpret"))


def segment_topk(keys, cap):
    """Fused stable top-k selection (ORDER BY / LIMIT entry point).
    keys: tuple of [N] operands, row 0 the invalid-sink flag, then
    sort keys most-significant first (descending pre-negated).
    Returns idx [cap] int32 — the stable lexsort prefix."""
    impl = _seg_impl()
    if impl == "jnp":
        return _ref.segment_topk(tuple(keys), cap)
    return _stk.segment_topk(tuple(keys), cap,
                             interpret=(impl == "interpret"))
