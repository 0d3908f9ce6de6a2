"""Fused segment-engine parity and seeded property tests (ISSUE 8).

Three layers:

  1. registry-driven kernel<->ref parity — iterates
     ``kernels.registry.KERNEL_REFS`` so a kernel cannot ship without
     its jnp reference being importable (KRN001's runtime half), and
     checks the two new segment kernels (fused aggregate, top-k
     selection) bit-for-bit against their twins under interpret mode;
  2. entry-point dispatch — ``kernels.ops`` must route by
     REPRO_FORCE_JNP / REPRO_KERNEL_INTERPRET and by the dense/scatter
     segment-space threshold without changing results;
  3. seeded property suites (``properties`` marker, host oracles):
     empty segments, one mega-segment, all-tie top-k stability,
     cap-exactly-full, and cap-overflow-triggers-regrowth at the
     service level.

Run the kernel slice on CPU with the interpreter (CI's --kernels
stage):  REPRO_KERNEL_INTERPRET=1 pytest tests/test_seg_kernels.py
"""
import importlib

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import ExecConfig, Executor, QueryService, compile_query
from repro.core.queries import ALL
from repro.kernels import ops, ref
from repro.kernels.registry import KERNEL_REFS
from repro.kernels.seg_aggregate import segmented_aggregate
from repro.kernels.seg_topk import segment_topk

def _agg_case(n, s, nc, rng):
    """Weather-like aggregate inputs: f32 columns holding integer
    tenths (the sensor archive's unit), some NaNs masked out through
    ``ok``, some invalid rows, some out-of-range segment ids. Integer
    values keep every partial sum exact in f32, so sums compare
    bitwise whatever order a backend reduces in."""
    vals = jnp.asarray(rng.integers(-400, 400, (n, nc)), jnp.float32)
    ok = jnp.asarray(rng.random((n, nc)) > 0.1)
    segs = jnp.asarray(rng.integers(-1, s + 2, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) > 0.2)
    return vals, ok, segs, valid


# ---------------------------------------------------------------------------
# 1. registry-driven parity
# ---------------------------------------------------------------------------


def test_registry_refs_resolve():
    """Every kernel entry point in KERNEL_REFS exists, and so does its
    declared jnp reference — the registry can't go stale silently."""
    for key, ref_name in KERNEL_REFS.items():
        mod_name, fn_name = key.split(".")
        mod = importlib.import_module(f"repro.kernels.{mod_name}")
        assert callable(getattr(mod, fn_name)), key
        assert callable(getattr(ref, ref_name)), (key, ref_name)


@pytest.mark.parametrize("n,s,bn,nc", [(512, 16, 128, 2),
                                       (256, 32, 256, 1),
                                       (384, 7, 128, 3)])
def test_segmented_aggregate_kernel_parity(n, s, bn, nc):
    """Interpreted Pallas kernel == jnp twin, bit for bit: counts and
    extrema always, sums because the data is integer-valued."""
    rng = np.random.default_rng([n, s, bn, nc])
    vals, ok, segs, valid = _agg_case(n, s, nc, rng)
    got = segmented_aggregate(vals, ok, segs, valid, s, block_n=bn,
                              interpret=True)
    want = ref.segmented_aggregate(vals, ok, segs, valid, s,
                                   block_n=bn)
    for g, w, what in zip(got, want, ("counts", "sums", "mins",
                                      "maxs")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=what)


@pytest.mark.parametrize("n,cap,nkeys", [(96, 8, 1), (200, 16, 2),
                                         (64, 64, 3)])
def test_segment_topk_kernel_parity(n, cap, nkeys):
    """Selection kernel == stable lexsort prefix, exactly — duplicate-
    heavy keys force the per-key tie refinement and row-index break."""
    rng = np.random.default_rng(100 + n)
    keys = [jnp.asarray(rng.integers(0, 2, n), jnp.int32)]  # flag
    for _ in range(nkeys):
        keys.append(jnp.asarray(rng.integers(-3, 3, n), jnp.int32))
    got = segment_topk(tuple(keys), cap, interpret=True)
    want = ref.segment_topk(tuple(keys), cap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_scatter_fallback_matches_dense_twin():
    """The large-segment-space scatter fallback agrees with the dense
    twin: counts/min/max bitwise always; sums bitwise on exactly-
    representable data (integer halves — no rounding, so accumulation
    association cannot show)."""
    rng = np.random.default_rng(5)
    n, s = 512, 48
    vals = jnp.asarray(rng.integers(-100, 100, (n, 2)) / 2.0,
                       jnp.float32)
    ok = jnp.asarray(rng.random((n, 2)) > 0.1)
    segs = jnp.asarray(rng.integers(-1, s + 2, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) > 0.2)
    a = ref.segmented_aggregate(vals, ok, segs, valid, s, block_n=128)
    b = ref.segmented_aggregate_scatter(vals, ok, segs, valid, s)
    for x, y, what in zip(a, b, ("counts", "sums", "mins", "maxs")):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


# ---------------------------------------------------------------------------
# 2. entry-point dispatch
# ---------------------------------------------------------------------------


def test_ops_dispatch_env(monkeypatch):
    """REPRO_KERNEL_INTERPRET routes the entry point through the
    interpreted kernel; REPRO_FORCE_JNP forces the twin; default CPU
    is the twin. All three agree bitwise."""
    vals, ok, segs, valid = _agg_case(512, 16, 2,
                                      np.random.default_rng(12))
    outs = {}
    for env in ({}, {"REPRO_KERNEL_INTERPRET": "1"},
                {"REPRO_FORCE_JNP": "1"}):
        monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
        monkeypatch.delenv("REPRO_FORCE_JNP", raising=False)
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        outs[tuple(env)] = ops.segmented_aggregate(vals, ok, segs,
                                                   valid, 16)
    base = outs[()]
    for key, got in outs.items():
        for g, w in zip(got, base):
            np.testing.assert_array_equal(np.asarray(g),
                                          np.asarray(w), err_msg=key)


def test_ops_count_only_and_topk_dispatch():
    """C == 0 (count-only group-by) returns empty column outputs; the
    top-k entry point matches the lexsort twin."""
    rng = np.random.default_rng(13)
    _, _, segs, valid = _agg_case(256, 8, 1, rng)
    c, s_, mn, mx = ops.segmented_aggregate(
        jnp.zeros((256, 0), jnp.float32), jnp.zeros((256, 0), bool),
        segs, valid, 8)
    assert s_.shape == (8, 0) and mn.shape == (8, 0)
    vld = np.asarray(valid) & (np.asarray(segs) >= 0) \
        & (np.asarray(segs) < 8)
    want = np.zeros(8)
    np.add.at(want, np.asarray(segs)[vld], 1.0)
    np.testing.assert_array_equal(np.asarray(c), want)

    keys = (jnp.asarray(rng.integers(0, 2, 64), jnp.int32),
            jnp.asarray(rng.integers(-5, 5, 64), jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(ops.segment_topk(keys, 8)),
        np.asarray(ref.segment_topk(keys, 8)))


AGGS = ("count", "sum", "min", "max")   # ops.segmented_aggregate's outputs


@pytest.mark.parametrize("agg", AGGS)
@pytest.mark.parametrize("nseg", [ops.SEG_DENSE_NSEG_MAX - 1,
                                  ops.SEG_DENSE_NSEG_MAX,
                                  ops.SEG_DENSE_NSEG_MAX + 1, 512, 2048])
def test_ops_segmented_aggregate_across_dense_cap(nseg, agg):
    """The group-by entry point on both sides of its dense/scatter
    threshold, and at the segment spaces the benchmark cells' plans
    use, equals the host per-segment reduction — empty segments
    included (every fourth segment receives no rows)."""
    rng = np.random.default_rng([nseg, AGGS.index(agg)])
    vals, ok, segs, valid = _agg_case(1024, nseg, 2, rng)
    segs = jnp.where(segs % 4 == 3, -1, segs)
    out = ops.segmented_aggregate(vals, ok, segs, valid, nseg)
    want = _host_agg(vals, ok, segs, valid, nseg)
    i = AGGS.index(agg)
    assert out[i].shape == want[i].shape
    assert (want[0] == 0).any()
    np.testing.assert_array_equal(np.asarray(out[i]), want[i])


# ---------------------------------------------------------------------------
# 3. seeded property suites (host oracles)
# ---------------------------------------------------------------------------

properties = pytest.mark.properties


def _host_agg(vals, ok, segs, valid, s):
    """Host oracle: per-segment count/sum/min/max over the valid,
    in-range, ok-masked rows."""
    vals, ok = np.asarray(vals), np.asarray(ok)
    segs, valid = np.asarray(segs), np.asarray(valid)
    nc = vals.shape[1]
    counts = np.zeros(s)
    sums = np.zeros((s, nc))
    mins = np.full((s, nc), np.inf)
    maxs = np.full((s, nc), -np.inf)
    for i in range(len(segs)):
        if not (valid[i] and 0 <= segs[i] < s):
            continue
        counts[segs[i]] += 1
        for c in range(nc):
            if ok[i, c]:
                sums[segs[i], c] += vals[i, c]
                mins[segs[i], c] = min(mins[segs[i], c], vals[i, c])
                maxs[segs[i], c] = max(maxs[segs[i], c], vals[i, c])
    return counts, sums, mins, maxs


@properties
@pytest.mark.parametrize("seed", range(3))
def test_property_empty_segments(seed):
    """Segments that receive no rows report count 0, sum 0, and the
    inf/-inf identity extrema — never garbage from other segments."""
    rng = np.random.default_rng(seed)
    n, s = 256, 24
    vals, ok, _, valid = _agg_case(n, s, 2, rng)
    # occupy only a few segments, leaving most empty
    occupied = rng.choice(s, 3, replace=False)
    segs = jnp.asarray(rng.choice(occupied, n), jnp.int32)
    got = ops.segmented_aggregate(vals, ok, segs, valid, s)
    want = _host_agg(vals, ok, segs, valid, s)
    empty = np.setdiff1d(np.arange(s), occupied)
    assert np.all(np.asarray(got[0])[empty] == 0)
    assert np.all(np.asarray(got[2])[empty] == np.inf)
    assert np.all(np.asarray(got[3])[empty] == -np.inf)
    np.testing.assert_array_equal(np.asarray(got[0]), want[0])
    np.testing.assert_array_equal(np.asarray(got[1]), want[1])
    np.testing.assert_array_equal(np.asarray(got[2]), want[2])
    np.testing.assert_array_equal(np.asarray(got[3]), want[3])


@properties
@pytest.mark.parametrize("seed", range(3))
def test_property_single_mega_segment(seed):
    """Every row in one segment: count equals the valid-row count and
    the sum equals the f32 sequential fold — exactly, because the
    integer-valued data leaves no rounding for the reduction order to
    change."""
    rng = np.random.default_rng(100 + seed)
    n, s = 512, 8
    vals, ok, _, valid = _agg_case(n, s, 1, rng)
    segs = jnp.full((n,), 5, jnp.int32)
    counts, sums, mins, maxs = ops.segmented_aggregate(
        vals, ok, segs, valid, s)
    nvalid = int(np.asarray(valid).sum())
    assert counts[5] == nvalid
    acc = np.float32(0.0)
    vn, okn, vld = (np.asarray(vals[:, 0]), np.asarray(ok[:, 0]),
                    np.asarray(valid))
    for i in range(n):
        if vld[i]:
            acc = np.float32(acc + (vn[i] if okn[i]
                                    else np.float32(0.0)))
    assert float(sums[5, 0]) == float(acc)
    assert np.all(np.asarray(counts)[np.arange(s) != 5] == 0)


@properties
@pytest.mark.parametrize("seed", range(3))
def test_property_all_tie_topk_stable(seed):
    """All keys equal: the selection must return row indices in
    ascending order — the stable-sort tiebreak, on both routes."""
    rng = np.random.default_rng(200 + seed)
    n, cap = 128, 16
    const = int(rng.integers(-5, 5))
    keys = (jnp.zeros((n,), jnp.int32),
            jnp.full((n,), const, jnp.int32))
    for route in (lambda: segment_topk(keys, cap, interpret=True),
                  lambda: ref.segment_topk(keys, cap)):
        np.testing.assert_array_equal(np.asarray(route()),
                                      np.arange(cap))


@properties
def test_property_cap_exactly_full(weather_db):
    """group_cap == the observed distinct-key count: the capacity is
    exactly full, which must NOT raise overflow (overflow is a
    (cap+1)-th key, not a full house)."""
    svc0 = QueryService(weather_db)
    exact = svc0.execute(ALL["Q9"]).rows()
    distinct = len(exact)
    ex = Executor(weather_db, ExecConfig(group_cap=distinct))
    rs = ex.run(compile_query(ALL["Q9"]))
    assert not rs.overflow_group_cap
    assert sorted(rs.rows()) == sorted(exact)


@properties
def test_property_cap_overflow_triggers_regrowth(weather_db):
    """group_cap below the distinct-key count raises exactly the
    group flag, and the service ladder regrows it to the exact result
    — on the fused engine path."""
    ex = Executor(weather_db, ExecConfig(group_cap=2))
    rs = ex.run(compile_query(ALL["Q9"]))
    assert rs.overflow and rs.overflow_group_cap
    assert not rs.overflow_scan and not rs.overflow_topk_cap

    svc = QueryService(weather_db, ExecConfig(group_cap=2))
    exact = QueryService(weather_db).execute(ALL["Q9"]).rows()
    got = svc.execute(ALL["Q9"]).rows()
    assert sorted(got) == sorted(exact)
    assert svc.stats.retries >= 1
    gcaps = {c.group_cap for c in svc.cached_configs()}
    assert len(gcaps) > 1 and 2 in gcaps
