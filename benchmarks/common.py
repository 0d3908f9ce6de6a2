"""Shared benchmark plumbing. One CSV row per measurement:
``table,name,metric,value,derived``.

Wall-clock caveat (single-core container): this box exposes ONE core,
so partition-parallel *wall* speedup cannot manifest; what the
speed-up/scale-up benches measure instead is per-partition work and
total throughput — the quantity that determines cluster scaling (the
chip benchmark's 4-chip cell measures it on a mesh). The paper's
qualitative claims (rewrites ~3x vs Saxon-style evaluation, ~2.5x vs
MapReduce-style staging) reproduce directly in wall time because they
are single-node effects.
"""
from __future__ import annotations

import time
from typing import Callable

import jax


def timeit(fn: Callable, *, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds over ``iters`` runs (after warmup)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def row(table: str, name: str, metric: str, value: float,
        derived: str = "") -> str:
    line = f"{table},{name},{metric},{value:.6g},{derived}"
    print(line, flush=True)
    return line
