"""Segmented aggregation kernel (Pallas TPU) — rule 4.2.2's local step.

Two-step aggregation reduces each partition locally before the global
exchange. When the aggregate is keyed (per-station, per-day — the
group-by queries), the local step is a segmented reduction. TPU-native
form: scatter has no good vector form, but a one-hot tile does — the
kernel builds the transposed one-hot ``(S, bn)`` (segment ids down the
sublanes, rows along the lanes) on the fly from a lane-dense row of
segment ids, and folds count/sum/min/max for every value column into
VMEM-resident ``(S, ·)`` outputs across the row blocks.

Exactness: every reduction runs on the VPU in f32 — masked selects and
lane reductions, no MXU pass — so no operand is ever rounded to bf16.
Counts and integer-valued sums below 2**24 (the sensor archive's
integer tenths) are exact in any order; min/max are exact always.

Layout (Mosaic's rules): segment ids are a (1, N) int32 row, values
and their masks (C, N) — lane-dense in N, with validity as 0/1 int32
rather than bool — so a vmap over partitions only adds a leading
dimension. N is padded to the row block (a multiple of 128 lanes) and
S to a multiple of 8 sublanes; the padding is masked out and sliced
off.

VMEM per step: a few (S, bn) f32 temporaries — 1 MB each at
S = 512, bn = 512.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
SUBLANE = 8


def _agg_kernel(seg_ref, vals_ref, ok_ref, cnt_ref, sum_ref, min_ref,
                max_ref, *, s: int, bn: int, nc: int):
    """Fused multi-column segment aggregation: one pass over the row
    blocks accumulates count/sum/min/max for every value column at
    once — no per-aggregate rescan, no full-width intermediate."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        max_ref[...] = jnp.full_like(max_ref, -jnp.inf)

    seg_ids = jax.lax.broadcasted_iota(jnp.int32, (s, bn), 0)
    oh = seg_ids == seg_ref[...]                       # (S, bn)
    cnt_ref[...] += jnp.sum(oh.astype(jnp.float32), axis=1, keepdims=True)
    v = vals_ref[...]                                  # (C, bn)
    ok = ok_ref[...] != 0
    for c in range(nc):   # static unroll over the value columns
        m = oh & ok[c:c + 1]                           # (S, bn)
        vc = v[c:c + 1]
        col = slice(c, c + 1)
        sum_ref[:, col] += jnp.sum(jnp.where(m, vc, 0.0), axis=1,
                                   keepdims=True)
        min_ref[:, col] = jnp.minimum(
            min_ref[:, col],
            jnp.min(jnp.where(m, vc, jnp.inf), axis=1, keepdims=True))
        max_ref[:, col] = jnp.maximum(
            max_ref[:, col],
            jnp.max(jnp.where(m, vc, -jnp.inf), axis=1, keepdims=True))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def segmented_aggregate(values: jax.Array, ok: jax.Array,
                        segments: jax.Array, valid: jax.Array,
                        num_segments: int, *, block_n: int = 512,
                        interpret: bool = False
                        ) -> tuple[jax.Array, jax.Array, jax.Array,
                                   jax.Array]:
    """values/ok: [N, C] (C >= 1); segments/valid: [N]. Returns
    (counts [S], sums [S, C], mins [S, C], maxs [S, C]).

    ``valid`` masks rows out of the segment space entirely (counts
    included); ``ok`` additionally masks per-column values (NaN
    exclusion) out of sum/min/max while the row still counts. Empty
    (segment, column) slots read +/-inf in mins/maxs — callers mask
    on counts. jnp twin: kernels.ref.segmented_aggregate."""
    assert block_n % LANE == 0, block_n
    n, nc = values.shape
    s = num_segments
    bn = min(block_n, _round_up(n, LANE))
    npad = _round_up(n, bn)
    spad = _round_up(s, SUBLANE)
    vld = valid & (segments >= 0) & (segments < s)
    # out-of-space and invalid rows get segment -1: no one-hot bit
    seg = jnp.where(vld, segments.astype(jnp.int32), -1)
    seg = jnp.pad(seg, (0, npad - n), constant_values=-1).reshape(1, npad)
    pad_cols = ((0, 0), (0, npad - n))
    vals_t = jnp.pad(values.astype(jnp.float32).T, pad_cols)
    ok_t = jnp.pad((ok & vld[:, None]).astype(jnp.int32).T, pad_cols)
    kernel = functools.partial(_agg_kernel, s=spad, bn=bn, nc=nc)
    out_spec = pl.BlockSpec((spad, nc), lambda i: (0, 0))
    cnt, sums, mins, maxs = pl.pallas_call(
        kernel,
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((nc, bn), lambda i: (0, i)),
            pl.BlockSpec((nc, bn), lambda i: (0, i)),
        ],
        out_specs=[pl.BlockSpec((spad, 1), lambda i: (0, 0)),
                   out_spec, out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((spad, 1), jnp.float32)]
        + [jax.ShapeDtypeStruct((spad, nc), jnp.float32)] * 3,
        interpret=interpret,
        name="seg_aggregate",
    )(seg, vals_t, ok_t)
    return cnt[:s, 0], sums[:s], mins[:s], maxs[:s]
