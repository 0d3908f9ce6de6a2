"""Pure-jnp oracles for every kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def block_join_probe(build_keys, build_valid, probe_keys, probe_valid):
    """First-match (build order) equi-join. O(NP*NB) dense compare."""
    np_ = probe_keys[0].shape[0]
    nb = build_keys[0].shape[0]
    eq = jnp.ones((np_, nb), bool)
    for pk, bk in zip(probe_keys, build_keys):
        eq &= pk[:, None] == bk[None, :]
    eq &= probe_valid[:, None] & build_valid[None, :]
    big = jnp.int32(2**31 - 1)
    pos = jnp.min(jnp.where(eq, jnp.arange(nb, dtype=jnp.int32)[None, :],
                            big), axis=1)
    matched = pos != big
    return jnp.where(matched, pos, -1), matched


def segmented_sum_count(values, segments, valid, num_segments):
    ok = valid & (segments >= 0) & (segments < num_segments)
    v = jnp.where(ok, values.astype(jnp.float32), 0.0)
    seg = jnp.where(ok, segments, num_segments)  # dump invalid past end
    sums = jnp.zeros((num_segments + 1,), jnp.float32).at[seg].add(v)
    cnts = jnp.zeros((num_segments + 1,), jnp.float32).at[seg].add(
        ok.astype(jnp.float32))
    return sums[:num_segments], cnts[:num_segments]


def segmented_aggregate(values, ok, segments, valid, num_segments, *,
                        block_n=512):
    """jnp twin of seg_aggregate.segmented_aggregate: the same
    blocked one-hot accumulation the kernel grid performs. This is the
    CPU fast path: no scatter, so XLA never lowers it to a serial
    while loop. The one-hot sums run at ``Precision.HIGHEST`` — at
    default precision a TPU matmul rounds its f32 operands to bf16,
    which holds integers exactly only up to 256 — so counts, min/max
    and integer-valued sums below 2**24 equal the kernel's bitwise.

    values/ok: [N, C]; segments/valid: [N] ->
    (counts [S], sums [S, C], mins [S, C], maxs [S, C])."""
    n, nc = values.shape
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    seg_all = segments.astype(jnp.int32)
    s = num_segments
    counts = jnp.zeros((s,), jnp.float32)
    sums = jnp.zeros((s, nc), jnp.float32)
    mins = jnp.full((s, nc), jnp.inf, jnp.float32)
    maxs = jnp.full((s, nc), -jnp.inf, jnp.float32)
    for b in range(n // bn):
        sl = slice(b * bn, (b + 1) * bn)
        seg, v = seg_all[sl], values[sl].astype(jnp.float32)
        vld = valid[sl] & (seg >= 0) & (seg < s)
        oh = (seg[:, None] == jnp.arange(s)[None, :]) & vld[:, None]
        ohf = oh.astype(jnp.float32)
        counts = counts + jnp.sum(ohf, axis=0)
        okm = ok[sl] & vld[:, None]
        sums = sums + jax.lax.dot_general(
            ohf, jnp.where(okm, v, 0.0), (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)
        m = oh[:, :, None] & okm[:, None, :]          # (bn, S, C)
        vb = v[:, None, :]
        mins = jnp.minimum(mins, jnp.min(
            jnp.where(m, vb, jnp.inf), axis=0))
        maxs = jnp.maximum(maxs, jnp.max(
            jnp.where(m, vb, -jnp.inf), axis=0))
    return counts, sums, mins, maxs


def segmented_aggregate_scatter(values, ok, segments, valid,
                                num_segments):
    """Large-segment-space fallback for the fused aggregate entry
    point (kernels.ops dispatches here above SEG_DENSE_NSEG_MAX): one
    scatter pass per output instead of the one-hot dense forms, whose
    O(N*S) cost overtakes the O(N) serial scatter once the segment
    space stops being small. Counts and min/max agree with the dense
    twin bit-for-bit (integer-valued counts; min/max are
    order-independent and exact); sums accumulate in row order, the
    same order the blocked dot_general consumes rows in."""
    n, nc = values.shape
    s = num_segments
    vld = valid & (segments >= 0) & (segments < s)
    sgi = jnp.where(vld, segments, s)       # dump invalid past the end
    counts = jnp.zeros((s + 1,), jnp.float32).at[sgi].add(
        vld.astype(jnp.float32))[:s]
    okm = ok & vld[:, None]
    v = values.astype(jnp.float32)
    sums = jnp.zeros((s + 1, nc), jnp.float32).at[sgi].add(
        jnp.where(okm, v, 0.0))[:s]
    mins = jnp.full((s + 1, nc), jnp.inf).at[sgi].min(
        jnp.where(okm, v, jnp.inf))[:s]
    maxs = jnp.full((s + 1, nc), -jnp.inf).at[sgi].max(
        jnp.where(okm, v, -jnp.inf))[:s]
    return counts, sums, mins, maxs


def segment_topk(keys, cap):
    """jnp twin of seg_topk.segment_topk: the stable lexsort prefix —
    literally the operand stack ``physical.topk_rows`` sorts, so the
    fused route and the legacy route produce identical indices by
    construction. keys[0] is the invalid-sink flag (primary), then
    the sort keys most-significant first."""
    order = jnp.lexsort(tuple(reversed(keys[1:])) + (keys[0],))
    return order[:cap].astype(jnp.int32)
