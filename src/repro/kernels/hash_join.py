"""Blocked equi-join probe kernel (Pallas TPU) — the paper's hot spot.

The paper's Hybrid Hash Join keeps one partition's build table in
memory and probes it per record. The TPU has no efficient scattered
hash table, but its VPU compares a (bp, bb) tile of probe×build keys
in one shot — so after the all_gather exchange has shrunk the build
side to a partition, the probe becomes a *blocked comparison*: grid
(NP/bp, NB/bb), each step matching a probe tile against a
VMEM-resident build tile and folding the first-match index. This is
the TPU-native reading of "hash partition + in-memory probe"
(DESIGN.md §2): partitioning does the hashing, the tile compare does
the probing.

Key columns are int32 (dictionary ids / packed dates — exact, no
collisions, see executor.key_arr). Up to 2 key components (the paper's
queries need station and station+date).

Layout (Mosaic's rules): every operand is a lane-dense (1, n) int32
row — validity included, as 0/1 int32 rather than bool — so a vmap
over partitions only adds a leading dimension the block specs index
with ``None``. Probe keys are turned from a row into a (bp, bb)
column broadcast once per probe tile (the ``j == 0`` step) with one
transpose, build rows broadcast along sublanes for free, and the
first-match fold runs elementwise into a (bp, bb) accumulator; the
one cross-lane reduction and the transpose back to a row happen on
the last build tile only.

VMEM per probe tile: (nkeys + 1) scratch tiles of (bp, bb) int32 —
1.5 MB at bp = 256, bb = 512 — plus the step's temporaries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 2**31 - 1  # python int: jnp constants would be captured tracers
LANE = 128


def _kernel(*refs, nkeys: int, bp: int, bb: int):
    probe_refs = refs[:nkeys]
    build_refs = refs[nkeys:2 * nkeys]
    pv_ref, bv_ref, pos_ref = refs[2 * nkeys:2 * nkeys + 3]
    pcol_ref, acc_ref = refs[2 * nkeys + 3:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for k in range(nkeys):
            # row (1, bp) -> (bp, bb) with [r, c] = probe[r]
            pcol_ref[k] = jnp.broadcast_to(probe_refs[k][...], (bb, bp)).T
        acc_ref[...] = jnp.full((bp, bb), BIG, jnp.int32)

    eq = build_refs[0][...] == pcol_ref[0]          # (1, bb) vs (bp, bb)
    for k in range(1, nkeys):
        eq = eq & (build_refs[k][...] == pcol_ref[k])
    eq = eq & (bv_ref[...] != 0)
    build_pos = j * bb + jax.lax.broadcasted_iota(jnp.int32, (bp, bb), 1)
    acc_ref[...] = jnp.minimum(acc_ref[...],
                               jnp.where(eq, build_pos, BIG))

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        first = jnp.min(acc_ref[...].T, axis=0, keepdims=True)  # (1, bp)
        miss = (first == BIG) | (pv_ref[...] == 0)
        pos_ref[...] = jnp.where(miss, -1, first)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _row(x: jax.Array, n: int) -> jax.Array:
    """[m] -> (1, n) int32, zero-padded: padded build rows carry
    validity 0 and never match; padded probe rows are sliced off."""
    x = x.astype(jnp.int32)
    return jnp.pad(x, (0, n - x.shape[0])).reshape(1, n)


def block_join_probe(build_keys: tuple[jax.Array, ...],
                     build_valid: jax.Array,
                     probe_keys: tuple[jax.Array, ...],
                     probe_valid: jax.Array, *,
                     block_p: int = 256, block_b: int = 512,
                     interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array]:
    """Returns (build_pos [NP] int32, matched [NP] bool). First match in
    build order wins (build keys unique in the paper's queries)."""
    nkeys = len(build_keys)
    assert nkeys == len(probe_keys) and 1 <= nkeys <= 2
    assert block_p % LANE == 0 and block_b % LANE == 0
    n_out = probe_keys[0].shape[0]
    n_build = build_keys[0].shape[0]
    # serving-path capacities are arbitrary (statistics-presized, then
    # doubled on regrowth) — round both sides up to lane-aligned
    # blocks and the block grid
    bp = min(block_p, _round_up(n_out, LANE))
    bb = min(block_b, _round_up(n_build, LANE))
    np_ = _round_up(n_out, bp)
    nb = _round_up(n_build, bb)
    kernel = functools.partial(_kernel, nkeys=nkeys, bp=bp, bb=bb)
    probe_spec = pl.BlockSpec((1, bp), lambda i, j: (0, i))
    build_spec = pl.BlockSpec((1, bb), lambda i, j: (0, j))
    pos = pl.pallas_call(
        kernel,
        grid=(np_ // bp, nb // bb),
        in_specs=[probe_spec] * nkeys + [build_spec] * nkeys
        + [probe_spec, build_spec],
        out_specs=probe_spec,
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((nkeys, bp, bb), jnp.int32),
                        pltpu.VMEM((bp, bb), jnp.int32)],
        interpret=interpret,
    )(*[_row(k, np_) for k in probe_keys],
      *[_row(k, nb) for k in build_keys],
      _row(probe_valid, np_), _row(build_valid, nb))
    pos = pos[0, :n_out]
    return pos, pos >= 0
