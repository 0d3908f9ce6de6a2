"""Segment top-k selection kernel (Pallas TPU) — the ORDER BY / LIMIT
tail of the fused segment-reduction family.

After the segmented reduce leaves [S] aggregate slots, a limit-k query
needs the first ``cap`` slots of the stable lexicographic order — a
selection, not a full sort. TPU has no native sort, but with k ~ cap
small and S VMEM-resident, ``cap`` rounds of masked lexicographic
argmin (VPU min-reductions over the (1, S) key rows, ties refined key
by key and finally broken on the row index) reproduce the stable
multi-key sort prefix exactly. The whole selection runs in one kernel
invocation: keys stay in VMEM, the output is the [cap] gather index
vector — no full-width sorted materialization.

Key rows arrive pre-oriented by the caller (descending keys negated,
row 0 = the invalid-sink flag, exactly the operand stack
``physical.topk_rows`` feeds ``jnp.lexsort``), so selection order ==
the jnp reference's stable lexsort order bit-for-bit. Keys must be
NaN-free (the executor's aggregate columns are — NaN values are
masked out of every aggregate before ordering).

The loop carries its selected-row mask as int32: Mosaic cannot carry
a bool vector through ``scf.for``.

VMEM: (nkeys + 2) · (1, N) rows ≈ a few KB at N ≤ 4096.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG_I32 = 2**31 - 1
LANE = 128


def _sentinel(dtype):
    # host-level dtype dispatch, not a traced value
    if jnp.issubdtype(dtype, jnp.floating):  # lint: allow(TRACE003)
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(BIG_I32, dtype)


def _topk_kernel(*refs, cap: int, nkeys: int, n: int, cpad: int):
    key_refs = refs[:nkeys]
    out_ref = refs[nkeys]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    iota_cap = jax.lax.broadcasted_iota(jnp.int32, (1, cpad), 1)

    def body(m, carry):
        selected, out = carry
        m0 = selected == 0
        # lexicographic argmin over the unselected rows: narrow the
        # tie set one key row at a time, then break on row index —
        # the stable-sort order
        for kr in key_refs:
            kv = kr[...]
            big = _sentinel(kv.dtype)
            cur = jnp.min(jnp.where(m0, kv, big))
            m0 = m0 & (kv == cur)
        idx_m = jnp.min(jnp.where(m0, iota, n))
        out = jnp.where(iota_cap == m, idx_m, out)
        selected = jnp.where(iota == idx_m, 1, selected)
        return selected, out

    sel0 = jnp.zeros((1, n), jnp.int32)
    out0 = jnp.zeros((1, cpad), jnp.int32)
    _, out = jax.lax.fori_loop(0, cap, body, (sel0, out0))
    out_ref[...] = out


def segment_topk(keys: tuple[jax.Array, ...], cap: int, *,
                 interpret: bool = False) -> jax.Array:
    """keys: tuple of [N] sort operands — row 0 the invalid-sink flag
    (int32 0/1), then the sort keys most-significant first, descending
    keys already negated. Returns idx [cap] int32: the first ``cap``
    positions of the stable ascending lexicographic order (ties break
    on row index). jnp twin: kernels.ref.segment_topk."""
    n = keys[0].shape[0]
    assert 0 < cap <= n, (cap, n)
    npad = -(-n // LANE) * LANE
    cpad = -(-cap // LANE) * LANE
    padded = []
    for i, k in enumerate(keys):
        # pad rows carry flag 2 — strictly greater than any real row's
        # 0/1 flag, so padding sorts behind every real row no matter
        # what the real keys are and can never enter the cap prefix
        # (cap <= n)
        fill = 2 if i == 0 else 0
        padded.append(jnp.pad(k, (0, npad - n),
                              constant_values=fill).reshape(1, npad))
    kernel = functools.partial(_topk_kernel, cap=cap, nkeys=len(keys),
                               n=npad, cpad=cpad)
    out = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec((1, npad), lambda: (0, 0))
                  for _ in padded],
        out_specs=pl.BlockSpec((1, cpad), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, cpad), jnp.int32),
        interpret=interpret,
    )(*padded)
    return out[0, :cap]
