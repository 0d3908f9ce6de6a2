"""Kernel <-> jnp-reference registry (KRN001's single source of truth).

Every Pallas entry point — a function in this package whose body
builds a ``pl.pallas_call`` — must declare its jnp reference here:
``"<module>.<function>" -> <function name in kernels/ref.py>``. The
KRN001 lint rule (core.analysis.lint) statically cross-checks this
literal against the package's AST, so a new kernel cannot land
without a reference, and the parity tests (tests/test_seg_kernels.py)
iterate the same table — a kernel can't silently skip parity either.

The mapping is a pure literal: the lint rule reads it without
importing jax.
"""
from __future__ import annotations

KERNEL_REFS: dict[str, str] = {
    "hash_join.block_join_probe": "block_join_probe",
    "seg_aggregate.segmented_aggregate": "segmented_aggregate",
    "seg_topk.segment_topk": "segment_topk",
}
