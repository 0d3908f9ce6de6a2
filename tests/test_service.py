"""QueryService: compiled-plan cache, overflow-driven capacity
regrowth, statistics-based cap pre-sizing (the adaptive layer that
keeps results exact while caps stay tight)."""
import pytest
from conftest import check_result

from repro.core import (ExecConfig, Executor, QueryOverflowError,
                        QueryService, compile_query)
from repro.core import algebra as A
from repro.core.queries import ALL, SCALAR


def check(rs, oracle, name):
    assert not rs.overflow
    check_result(rs, oracle, name)


def true_scan_size(db, plan) -> int:
    """Largest per-partition scan cardinality in the plan (the per-tag
    build-time counts are exact for these child paths)."""
    return max(db.stats[op.collection].path_match_bound(db.names, op.path)
               for op in A.walk(plan) if isinstance(op, A.DataScan))


@pytest.mark.parametrize("name", list(ALL))
def test_tiny_caps_regrow_to_exact(weather_db, oracle, name):
    """Seeded with a scan cap 1/10th of the true result size (and a
    width-1 join bucket), the service must regrow to an exact result —
    and serve the repeat from the plan cache without recompiling."""
    plan = compile_query(ALL[name])
    tiny = max(1, true_scan_size(weather_db, plan) // 10)
    svc = QueryService(weather_db,
                       ExecConfig(scan_cap=tiny, join_bucket=1),
                       presize=False)
    rs = svc.execute(plan)
    check(rs, oracle, name)
    assert svc.stats.retries >= 1      # the tiny cap did overflow
    # second execution: cache hit, zero new compiles (compile-counter
    # on both the service and the underlying executor)
    snap = svc.stats.snapshot()
    ex_compiles = svc.executor.compile_count
    rs2 = svc.execute(plan)
    check(rs2, oracle, name)
    delta = svc.stats.diff(snap)
    assert delta.compiles == 0
    assert svc.executor.compile_count == ex_compiles
    assert delta.cache_hits >= 1


@pytest.mark.parametrize("name", list(ALL))
def test_presized_caps_avoid_retries(weather_db, oracle, name):
    """Build-time statistics pre-size first-shot caps: each paper
    query runs exactly with zero overflow retries, and none needed the
    padded-table fallback capacity."""
    svc = QueryService(weather_db)
    check(svc.execute(ALL[name]), oracle, name)
    assert svc.stats.retries == 0
    assert svc.stats.executions == 1
    tight = [c.scan_cap for c in svc.cached_configs()]
    assert tight and all(cap is not None and cap < svc._scan_ceiling
                         for cap in tight), tight


def test_repeated_query_hits_cache(weather_db, oracle):
    svc = QueryService(weather_db)
    check(svc.execute(ALL["Q4"]), oracle, "Q4")
    snap = svc.stats.snapshot()
    check(svc.execute(ALL["Q4"]), oracle, "Q4")
    delta = svc.stats.diff(snap)
    assert delta.compiles == 0
    assert delta.cache_hits == 1
    assert svc.cache_size() == 1


def test_regrowth_touches_only_saturated_capacity(weather_db, oracle):
    """A scan-only overflow must not inflate the join bucket: the
    per-stage flags drive targeted regrowth."""
    svc = QueryService(weather_db, ExecConfig(scan_cap=4),
                       presize=False)
    check(svc.execute(ALL["Q2"]), oracle, "Q2")     # join-free query
    assert svc.stats.retries >= 1
    buckets = {c.join_bucket for c in svc.cached_configs()}
    assert buckets == {4}, buckets


def test_per_stage_overflow_flags(weather_db):
    """Executor surfaces scan-cap vs join-bucket overflow separately."""
    ex = Executor(weather_db, ExecConfig(scan_cap=8))
    rs = ex.run(compile_query(ALL["Q2"]))
    assert rs.overflow and rs.overflow_scan and not rs.overflow_join


def test_distinct_configs_get_distinct_cache_entries(weather_db):
    svc = QueryService(weather_db, presize=False)
    plan = compile_query(ALL["Q4"])
    svc.execute(plan)
    pq = svc.prepare(plan)
    svc2_cfg = ExecConfig(scan_cap=64)
    cp_a = svc.compiled(pq.plan, svc.base_config, sig=pq.signature,
                        param_specs=pq.specs)
    cp_b = svc.compiled(pq.plan, svc2_cfg, sig=pq.signature,
                        param_specs=pq.specs)
    assert cp_a is not cp_b
    assert svc.cache_size() == 2


def test_donated_plan_spends_the_executor(weather_db):
    """A donated run gives the executor's shared table buffers to that
    call: reusing the plan OR running any other plan on that executor
    must be refused, not dereference dead buffers."""
    ex = Executor(weather_db)
    cp = ex.compile(compile_query(ALL["Q4"]), donate=True)
    ex.run_compiled(cp)
    with pytest.raises(RuntimeError, match="donated"):
        ex.run_compiled(cp)
    with pytest.raises(RuntimeError, match="donated"):
        ex.run(compile_query(ALL["Q2"]))    # different, fresh plan


def test_overflow_error_when_growth_exhausted(weather_db):
    """max_retries=0 with a hopeless cap: the service must refuse to
    return a truncated result."""
    svc = QueryService(weather_db, ExecConfig(scan_cap=2),
                       presize=False, max_retries=0)
    with pytest.raises(QueryOverflowError):
        svc.execute(ALL["Q2"])


def test_lru_eviction_capacity_one(weather_db, oracle):
    """Capacity-1 cache: the second template evicts the first; re-
    executing the first re-prepares and recompiles, and every result
    stays exact throughout."""
    svc = QueryService(weather_db, cache_capacity=1)
    check(svc.execute(ALL["Q4"]), oracle, "Q4")
    assert svc.cache_size() == 1
    check(svc.execute(ALL["Q2"]), oracle, "Q2")     # evicts Q4
    assert svc.cache_size() == 1
    assert svc.stats.evictions == 1
    snap = svc.stats.snapshot()
    check(svc.execute(ALL["Q4"]), oracle, "Q4")     # must recompile
    assert svc.stats.diff(snap).compiles == 1
    assert svc.cache_size() == 1


def test_lru_recency_order(weather_db, oracle):
    """Touching an entry protects it: with capacity 2, re-executing
    the older template before inserting a third evicts the middle one,
    not the re-touched one."""
    svc = QueryService(weather_db, cache_capacity=2)
    check(svc.execute(ALL["Q4"]), oracle, "Q4")
    check(svc.execute(ALL["Q2"]), oracle, "Q2")
    check(svc.execute(ALL["Q4"]), oracle, "Q4")     # touch Q4
    check(svc.execute(ALL["Q1"]), oracle, "Q1")     # evicts Q2
    snap = svc.stats.snapshot()
    check(svc.execute(ALL["Q4"]), oracle, "Q4")     # still cached
    assert svc.stats.diff(snap).compiles == 0
    check(svc.execute(ALL["Q2"]), oracle, "Q2")     # was evicted
    assert svc.stats.diff(snap).compiles == 1


def test_group_cap_bounds_segment_space(weather_db):
    """A tiny group_cap overflows on its own flag — not the scan cap,
    not the join machinery."""
    ex = Executor(weather_db, ExecConfig(group_cap=2))
    rs = ex.run(compile_query(ALL["Q9"]))
    assert rs.overflow and rs.overflow_group_cap
    assert not rs.overflow_scan and not rs.overflow_join
    assert not rs.overflow_join_cap


@pytest.mark.parametrize("name", ["Q9", "Q10"])
def test_group_cap_regrows_to_exact(weather_db, oracle, name):
    """Started with group_cap=2 on a higher-cardinality key (8
    stations), the regrowth ladder converges to an exact result, and
    only group_cap grew."""
    svc = QueryService(weather_db, ExecConfig(group_cap=2))
    check(svc.execute(ALL[name]), oracle, name)
    assert svc.stats.retries >= 1
    gcaps = {c.group_cap for c in svc.cached_configs()}
    assert len(gcaps) > 1 and 2 in gcaps
    assert max(gcaps) <= svc._group_ceiling
    buckets = {c.join_bucket for c in svc.cached_configs()}
    assert buckets == {4}, buckets   # join machinery never inflated


def test_group_regrowth_shares_plans_across_variants(weather_db):
    """The regrowth ladder must ride the parameter-erased cache: a
    second constant-variant of a regrown group-by template reuses both
    the grown config (_good_cfg) and the compiled executable — zero
    new compiles, no exact-signature fallback."""
    svc = QueryService(weather_db, ExecConfig(group_cap=2))
    svc.execute(ALL["Q9"])
    assert svc.stats.retries >= 1
    snap = svc.stats.snapshot()
    variant = ALL["Q9"].replace("TMAX", "TMIN")
    rs = svc.execute(variant)
    assert not rs.overflow and rs.rows()
    delta = svc.stats.diff(snap)
    assert delta.compiles == 0                 # shared executable
    assert delta.retries == 0                  # ladder skipped
    assert delta.cache_hits >= 1


def test_presize_sizes_group_cap_from_statistics(weather_db, oracle):
    """Build-time distinct-key statistics pre-size the segment space:
    group-by queries run retry-free with a dictionary-independent
    group_cap."""
    svc = QueryService(weather_db)
    for name in ("Q9", "Q10"):
        check(svc.execute(ALL[name]), oracle, name)
    assert svc.stats.retries == 0
    gcaps = [c.group_cap for c in svc.cached_configs()]
    assert all(g is not None and g < len(weather_db.strings)
               for g in gcaps), gcaps


def test_regrowth_recompiles_visible_in_stats(weather_db):
    """Satellite fix: every regrowth-retry recompile — join_cap and
    group_cap ladders included — must be counted in stats.compiles
    (the exact mirror of the executor's compile_count), not just the
    first compile of a template."""
    svc = QueryService(weather_db, ExecConfig(join_cap=2))
    svc.execute(ALL["Q6"])                      # join_cap ladder
    assert svc.stats.retries >= 1
    assert svc.stats.compiles == svc.executor.compile_count
    assert svc.stats.compiles >= 2              # initial + regrowth

    svc2 = QueryService(weather_db, ExecConfig(group_cap=2))
    svc2.execute(ALL["Q9"])                     # group_cap ladder
    assert svc2.stats.retries >= 1
    assert svc2.stats.compiles == svc2.executor.compile_count
    assert svc2.stats.compiles >= 2


def test_join_cap_bounds_probe_output(weather_db):
    """A tiny join_cap overflows on its own flag — not the scan cap,
    not the bucket width."""
    ex = Executor(weather_db, ExecConfig(join_cap=2))
    rs = ex.run(compile_query(ALL["Q6"]))
    assert rs.overflow and rs.overflow_join_cap
    assert not rs.overflow_scan and not rs.overflow_join


def test_join_cap_regrows_to_exact(weather_db, oracle):
    """The service regrows a saturated join_cap like a scan cap: the
    result is exact and only join_cap grew."""
    svc = QueryService(weather_db, ExecConfig(join_cap=2))
    check(svc.execute(ALL["Q6"]), oracle, "Q6")
    assert svc.stats.retries >= 1
    caps = {c.join_cap for c in svc.cached_configs()}
    assert len(caps) > 1 and 2 in caps
    buckets = {c.join_bucket for c in svc.cached_configs()}
    assert buckets == {4}, buckets   # bucket never inflated
    # an adequate join_cap still yields exact results without retries
    svc2 = QueryService(weather_db, ExecConfig(join_cap=max(
        c for c in caps if c is not None)))
    check(svc2.execute(ALL["Q6"]), oracle, "Q6")
    assert svc2.stats.retries == 0


# ---------------------------------------------------------------------------
# warm-up: XLA compiles on a thread pool
# ---------------------------------------------------------------------------

WARM = ("Q2", "Q4", "Q9", "Q11")


def _compile_threads(monkeypatch, fail_at=None):
    """Record the thread of every warm-up compile; the ``fail_at``-th
    one raises instead."""
    import threading

    from repro.core import service
    real, seen = service._timed_compile, []
    lock = threading.Lock()

    def timed(lowered):
        with lock:
            seen.append(threading.current_thread().name)
            n = len(seen)
        if n == fail_at:
            raise RuntimeError("compile refused")
        return real(lowered)
    monkeypatch.setattr(service, "_timed_compile", timed)
    return seen


def test_concurrent_warmup_matches_one_at_a_time(weather_db, oracle,
                                                 monkeypatch):
    import os

    from repro.core.obs.trace import Tracer
    threads = _compile_threads(monkeypatch)
    tracer = Tracer()
    warm = QueryService(weather_db, tracer=tracer)
    summary = warm.warmup([ALL[n] for n in WARM])
    one = QueryService(weather_db)
    rows = {n: one.execute(ALL[n]).rows() for n in WARM}

    assert summary["compiles"] == warm.stats.compiles == len(WARM)
    assert warm.executor.compile_count == one.stats.compiles == len(WARM)
    assert list(warm._cache) == list(one._cache)
    assert len(threads) == len(WARM)
    assert all(t.startswith("vxq-compile") for t in threads)
    spans = [s for s in tracer.records if s.name == "compile"]
    assert len(spans) == len(WARM)
    (span,) = [s for s in tracer.records if s.name == "warmup"]
    assert span.args == {"variants": len(WARM),
                         "workers": min(len(WARM), os.cpu_count())}
    snap = warm.stats.snapshot()
    for n in WARM:
        rs = warm.execute(ALL[n])
        assert rs.rows() == rows[n]
        check(rs, oracle, n)
    assert warm.stats.diff(snap).compiles == 0


def test_warmup_keeps_other_plans_when_a_compile_fails(weather_db,
                                                       monkeypatch):
    _compile_threads(monkeypatch, fail_at=2)
    svc = QueryService(weather_db)
    with pytest.raises(RuntimeError, match="compile refused"):
        svc.warmup([ALL[n] for n in WARM])
    assert svc.stats.compiles == svc.cache_size() == len(WARM) - 1
    snap = svc.stats.snapshot()
    for n in WARM:
        svc.execute(ALL[n])
    assert svc.stats.diff(snap).compiles == 1
