"""Observability layer: span tracer on dual clocks, metrics registry,
per-operator query profiles, trace export (core/obs/).

Covers the tentpole contracts:

* spans nest and stamp wall + virtual time; the NULL/disabled tracers
  are no-ops; ``chrome_trace`` validates against the Chrome/Perfetto
  ``trace_event`` schema (and the validator itself rejects malformed
  events);
* replaying the same seeded multi-tenant trace through two fresh
  services yields byte-identical virtual-time span logs — wall time
  never leaks into the deterministic view;
* histograms merge order-invariantly (property-tested);
* ``QueryService.explain(profile=True)`` produces an operator-
  annotated profile for every Q1-Q12 on the prepared, batched and
  scheduled paths;
* SLO misses carry per-tenant and per-cause attribution;
* the OBS001/OBS002 lint keeps stats increments and the metrics
  registry in sync;
* ``execute`` runs its stages (prepare, bind, launch, wait, fetch,
  then decode) in spans that an enabled tracer mirrors into the JAX
  profiler, costs one ``device_get`` with tracing off, and counts
  prepares, fetched bytes, decoded rows and exchange bytes.
"""
import json
import math
import os
import random

import jax
import numpy as np
import pytest

import repro
from repro.core import QueryService
from repro.core.obs import trace as obs_trace
from repro.core.obs.metrics import (DEFAULT_BUCKETS, Counter, EventSink,
                                    Gauge, Histogram, MetricsRegistry,
                                    REGISTERED_STATS, stats_diff,
                                    stats_snapshot)
from repro.core.obs.trace import (NULL_TRACER, Tracer, sig_digest,
                                  validate_trace_events)
from repro.core.queries import ALL


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_spans_nest_and_stamp_wall_time():
    tr = Tracer()
    with tr.span("outer", cat="service") as outer:
        with tr.span("inner", cat="service") as inner:
            inner.set(k=1)
        tr.event("tick", cat="service", n=2)
    assert [s.name for s in tr.records] == ["outer", "inner", "tick"]
    assert inner.parent == outer.sid
    assert tr.records[2].parent == outer.sid
    assert outer.wall_dur is not None and outer.wall_dur >= 0
    assert outer.vt0 is None            # no clock bound
    assert inner.args == {"k": 1}


def test_span_records_error_type():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    assert tr.records[0].args["error"] == "ValueError"
    assert tr._stack == []              # stack unwound


def test_disabled_and_null_tracers_record_nothing():
    for tr in (Tracer(enabled=False), NULL_TRACER):
        with tr.span("a", cat="service") as sp:
            sp.set(k=1)
        tr.event("b")
        assert tr.records == []


def test_virtual_stamps_with_bound_clock():
    from repro.core.serving.queue import VirtualClock
    clk = VirtualClock()
    tr = Tracer()
    tr.bind_clock(clk)
    with tr.span("s", cat="serving"):
        clk.advance(1.5)
    s = tr.records[0]
    assert s.vt0 == 0.0 and s.vt1 == 1.5


def test_chrome_trace_validates_and_leads_with_metadata():
    from repro.core.serving.queue import VirtualClock
    clk = VirtualClock()
    tr = Tracer(clock=clk)
    with tr.span("s", cat="serving", sig="abc"):
        clk.advance(2.0)
        tr.event("i", cat="serving")
    for clock in ("wall", "virtual"):
        ev = tr.chrome_trace(clock=clock)
        assert ev[0]["ph"] == "M"
        assert validate_trace_events(ev) == []
        json.dumps(ev)                  # JSON-ready end to end
    ev = tr.chrome_trace(clock="virtual")
    span = next(e for e in ev if e["ph"] == "X")
    assert span["dur"] == pytest.approx(2.0 * 1e6)


def test_virtual_clock_spans_excluded_from_wallless_virtual_export():
    tr = Tracer()                       # no clock bound
    with tr.span("host-only", cat="prepare"):
        pass
    assert len(tr.chrome_trace(clock="virtual")) == 1   # metadata only
    assert len(tr.chrome_trace(clock="wall")) == 2


@pytest.mark.parametrize("bad,needle", [
    ({"name": "x", "pid": 1, "tid": 0}, "ph"),
    ({"ph": "X", "pid": 1, "tid": 0, "ts": 0, "dur": 1}, "name"),
    ({"ph": "X", "name": "x", "pid": 1, "tid": 0, "dur": 1}, "ts"),
    ({"ph": "X", "name": "x", "pid": 1, "tid": 0, "ts": 0}, "dur"),
    ({"ph": "X", "name": "x", "pid": 1, "tid": 0, "ts": 0,
      "dur": -1}, "dur"),
    ({"ph": "i", "name": "x", "pid": 1, "tid": 0, "ts": 0}, "scope"),
    ({"ph": "i", "name": "x", "pid": 1, "tid": 0, "ts": 0,
      "s": "z"}, "scope"),
    ({"ph": "X", "name": "x", "pid": 1, "tid": 0, "ts": 0, "dur": 1,
      "args": 3}, "args"),
])
def test_validator_rejects_malformed_events(bad, needle):
    problems = validate_trace_events([bad])
    assert problems and needle in problems[0]


def test_validator_rejects_non_list():
    assert validate_trace_events({"ph": "X"})


def test_sig_digest_stable_and_short():
    assert sig_digest("abc") == sig_digest("abc")
    assert len(sig_digest(("a", 1))) == 8


def test_ambient_tracer_stack():
    tr = Tracer()
    assert obs_trace.current() is NULL_TRACER
    with obs_trace.using(tr):
        assert obs_trace.current() is tr
        obs_trace.current().event("e", cat="host")
    assert obs_trace.current() is NULL_TRACER
    assert [s.name for s in tr.records] == ["e"]


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_counter_and_labels():
    c = Counter("requests_total")
    c.inc()
    c.inc(2)
    assert c.value == 3
    c.labels(tenant="a").inc()
    c.labels(tenant="a").inc()
    c.labels(tenant="b").inc()
    samples = dict((tuple(sorted(lab.items())), v)
                   for lab, v in c.samples())
    assert samples[(("tenant", "a"),)] == 2
    assert samples[(("tenant", "b"),)] == 1
    with pytest.raises(AssertionError):
        c.inc(-1)


def test_gauge_lazy_fn():
    g = Gauge("cache_entries", fn=lambda: 7)
    assert list(g.samples()) == [({}, 7)]


def test_histogram_observe_and_percentiles():
    h = Histogram("lat", buckets=(0.1, 1.0, math.inf))
    for v in (0.05, 0.05, 0.5, 2.0):
        h.observe(v)
    assert h.count == 4 and h.counts == [2, 1, 1]
    assert h.percentile(0.50) == 0.1
    assert h.percentile(0.99) == 1.0    # inf bucket -> largest finite
    assert h.summary()["count"] == 4
    assert Histogram("empty").percentile(0.99) == 0.0


@pytest.mark.properties
def test_histogram_merge_is_order_invariant():
    """Partition one seeded sample into k histograms, merge in many
    shuffled orders: identical counts/sum/count — and identical to
    observing everything in one histogram."""
    rng = random.Random(42)
    values = [rng.lognormvariate(-2, 2) for _ in range(400)]
    one = Histogram("h")
    for v in values:
        one.observe(v)
    for trial in range(5):
        parts = [Histogram("h") for _ in range(7)]
        for i, v in enumerate(values):
            parts[i % 7].observe(v)
        rng.shuffle(parts)
        acc = Histogram("h")
        for p in parts:
            acc.merge(p)
        assert acc.counts == one.counts
        assert acc.count == one.count
        assert acc.sum == pytest.approx(one.sum)
        assert acc.percentile(0.95) == one.percentile(0.95)


def test_histogram_merge_rejects_different_layouts():
    with pytest.raises(AssertionError):
        Histogram("a").merge(Histogram("b", buckets=(1.0, math.inf)))


def test_registry_exposition_and_binding(weather_db_small):
    svc = QueryService(weather_db_small)
    svc.execute(ALL["Q4"])
    text = svc.metrics.exposition()
    assert "service_executions_total 1" in text
    assert "# TYPE service_compiles_total counter" in text
    h = svc.metrics.histogram("demo_latency")
    h.observe(0.2)
    text = svc.metrics.exposition()
    assert 'demo_latency_bucket{le="+Inf"} 1' in text
    assert "demo_latency_count 1" in text
    d = svc.metrics.to_dict()
    assert d["service_executions_total"] == 1
    assert d["demo_latency"]["count"] == 1


def test_register_stats_rejects_unregistered_field():
    import dataclasses

    @dataclasses.dataclass
    class Rogue:
        bogus_counter: int = 0

    reg = MetricsRegistry()
    with pytest.raises(AssertionError, match="bogus_counter"):
        reg.register_stats("rogue", Rogue())


def test_registered_stats_dict_fields_expose_labeled_samples():
    from repro.core.service import ServiceStats
    st = ServiceStats()
    st.overflows_by_cap["scan_cap"] = 3
    reg = MetricsRegistry()
    reg.register_stats("service", st)
    assert ('service_overflows_total{cap="scan_cap"} 3'
            in reg.exposition())


def test_stats_snapshot_diff_including_dict_fields():
    from repro.core.serving.scheduler import RuntimeStats
    st = RuntimeStats()
    st.submitted = 2
    st.slo_misses_by_tenant["a"] = 1
    snap = stats_snapshot(st)
    st.submitted = 5
    st.slo_misses_by_tenant["a"] = 2
    st.slo_misses_by_tenant["b"] = 1
    d = stats_diff(st, snap)
    assert d.submitted == 3
    assert d.slo_misses_by_tenant == {"a": 1, "b": 1}
    snap.slo_misses_by_tenant["a"] = 99   # snapshot is a real copy
    assert st.slo_misses_by_tenant["a"] == 2


def test_event_sink_jsonl():
    sink = EventSink()
    sink.emit("gate", suite="obs", passed=True)
    line = json.loads(sink.jsonl().splitlines()[0])
    assert line == {"event": "gate", "suite": "obs", "passed": True}


def test_default_buckets_are_sorted_and_end_with_inf():
    assert DEFAULT_BUCKETS[-1] == math.inf
    assert tuple(sorted(DEFAULT_BUCKETS)) == DEFAULT_BUCKETS


# ---------------------------------------------------------------------------
# service + runtime integration
# ---------------------------------------------------------------------------

_TRAFFIC = [
    (0.0, "alpha", ALL["Q1"]),
    (0.2, "beta", ALL["Q4"]),
    (0.4, "alpha", ALL["Q1"]),
    (0.9, "beta", ALL["Q2"]),
    (1.1, "alpha", ALL["Q4"]),
    (2.5, "beta", ALL["Q1"]),
]


def _replay(db):
    tr = Tracer()
    svc = QueryService(db, tracer=tr)
    rt = svc.runtime(window=0.5, max_fill=4)
    for at, tenant, text in _TRAFFIC:
        rt.submit(text, tenant=tenant, at=at)
    tickets = rt.drain()
    return tr, svc, rt, tickets


def test_trace_replay_determinism(weather_db_small):
    """Same seeded multi-tenant trace through two fresh services:
    byte-identical virtual-time span logs (wall time is excluded from
    the deterministic view by construction)."""
    tr_a, _, _, tk_a = _replay(weather_db_small)
    tr_b, _, _, tk_b = _replay(weather_db_small)
    log_a, log_b = tr_a.virtual_log(), tr_b.virtual_log()
    assert log_a, "expected virtual-time records"
    assert "\n".join(log_a) == "\n".join(log_b)
    assert [t.completion for t in tk_a] == [t.completion for t in tk_b]
    # and the virtual-clock chrome export validates on both runs
    for tr in (tr_a, tr_b):
        assert validate_trace_events(tr.chrome_trace("virtual")) == []
        assert validate_trace_events(tr.chrome_trace("wall")) == []


def test_serving_spans_cover_the_pipeline(weather_db_small):
    tr, svc, rt, tickets = _replay(weather_db_small)
    names = {s.name for s in tr.records}
    for expected in ("prepare", "verify", "compile", "admit",
                     "window-close", "dispatch", "execute"):
        assert expected in names, expected
    # every serving-stage record carries virtual stamps
    for s in tr.records:
        if s.cat == "serving":
            assert s.vt0 is not None
    # window-close instants carry their cause
    causes = {s.args.get("cause") for s in tr.records
              if s.name == "window-close"}
    assert causes <= {"deadline", "fill", "flush"} and causes


def test_slo_miss_attribution(weather_db_small):
    svc = QueryService(weather_db_small)
    rt = svc.runtime(window=1.0)
    # cold submit with an impossible SLO: the completing dispatch
    # pays the template's first compile -> compile-on-path
    t_cold = rt.submit(ALL["Q4"], tenant="a", at=0.0, slo=0.5)
    rt.drain()
    assert t_cold.completion > t_cold.deadline
    assert t_cold.slo_cause == "compile-on-path"
    # warm repeat, same impossible SLO: nothing compiles, nothing
    # regrows -> the miss is pure queueing
    rt2 = svc.runtime(window=1.0)
    t_warm = rt2.submit(ALL["Q4"], tenant="b", at=0.0, slo=0.5)
    rt2.drain()
    assert t_warm.slo_cause == "queued-behind"
    assert rt2.stats.slo_misses_by_tenant == {"b": 1}
    assert rt2.stats.slo_miss_causes == {"queued-behind": 1}
    # breakdowns sum to the total
    assert (sum(rt2.stats.slo_misses_by_tenant.values())
            == rt2.stats.slo_misses == 1)


def test_runtime_latency_histograms_fill(weather_db_small):
    _, svc, rt, tickets = _replay(weather_db_small)
    text = svc.metrics.exposition()
    assert "runtime_latency_vs_bucket" in text
    assert 'tenant="alpha"' in text and 'tenant="beta"' in text
    assert "runtime_submitted_total 6" in text
    h = svc.metrics.histogram("runtime_latency_vs")
    total = sum(c.count for c in h._children.values())
    assert total == len(tickets)


def test_overflows_by_cap_attributes_regrowth(weather_db_small):
    from repro.core import ExecConfig
    svc = QueryService(weather_db_small, ExecConfig(scan_cap=4),
                       presize=False)
    svc.execute(ALL["Q2"])
    assert svc.stats.retries >= 1
    assert set(svc.stats.overflows_by_cap) == {"scan_cap"}
    assert svc.stats.overflows_by_cap["scan_cap"] == svc.stats.retries


# ---------------------------------------------------------------------------
# explain / per-operator profiles
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def profiled_svc(weather_db_small):
    return QueryService(weather_db_small, cache_capacity=128)


@pytest.mark.parametrize("name", list(ALL))
@pytest.mark.parametrize("path", ["prepared", "batched", "scheduled"])
def test_explain_profiles_every_query(profiled_svc, name, path):
    prof = profiled_svc.explain(ALL[name], profile=True, path=path)
    assert prof.path == path
    scan = prof.op("DATASCAN")
    assert scan.rows is not None and scan.rows > 0
    assert scan.cap == "scan_cap" and scan.cap_value
    assert scan.rows_peak is not None
    assert 0 < scan.utilization <= 1.0   # presized, no overflow
    assert prof.compile_s is not None and prof.compile_s >= 0
    assert prof.execute_s is not None and prof.execute_s >= 0
    # fused ops carry no row count and say so
    for o in prof.ops:
        if o.fused:
            assert o.rows is None
    text = prof.render()
    assert "rows=" in text and "util=" in text
    assert f"path={path}" in text


def test_explain_static_has_caps_but_no_rows(profiled_svc):
    prof = profiled_svc.explain(ALL["Q11"])
    assert prof.path == "static"
    assert all(o.rows is None for o in prof.ops)
    limit = prof.op("LIMIT")
    assert limit.cap == "topk_cap"      # fused sort reports at LIMIT
    orderby = prof.op("ORDER-BY")
    assert orderby.fused and orderby.cap is None
    assert "static" in prof.render()


def test_explain_profile_shows_regrowth(weather_db_small):
    from repro.core import ExecConfig
    svc = QueryService(weather_db_small, ExecConfig(scan_cap=4),
                       presize=False)
    prof = svc.explain(ALL["Q2"], profile=True)
    assert prof.retries >= 1
    assert any(cap == "scan_cap" for cap, _, _ in prof.regrowths)
    assert "regrew scan_cap" in prof.render()
    # the regrown run is exact: the final config's cap fits the rows
    scan = prof.op("DATASCAN")
    assert not scan.overflow
    assert scan.rows_peak <= scan.cap_value


def test_explain_profile_compiles_do_not_pollute_serving_cache(
        profiled_svc):
    """Profile variants key separately: a profiled explain never
    replaces the serving-path executable, and the compile-counter
    invariant (stats.compiles == executor.compile_count) holds."""
    svc = profiled_svc
    svc.execute(ALL["Q4"])
    snap = svc.stats.snapshot()
    svc.explain(ALL["Q4"], profile=True)
    first = svc.stats.diff(snap).compiles
    svc.explain(ALL["Q4"], profile=True)     # profile variant cached
    assert svc.stats.diff(snap).compiles == first
    assert svc.stats.compiles == svc.executor.compile_count
    # the serving path is still a pure cache hit
    snap = svc.stats.snapshot()
    svc.execute(ALL["Q4"])
    assert svc.stats.diff(snap).compiles == 0


# ---------------------------------------------------------------------------
# lint: metrics-registry completeness
# ---------------------------------------------------------------------------


def _src_root() -> str:
    # repro may be a namespace package (__file__ None): use __path__
    return os.path.dirname(next(iter(repro.__path__)))


@pytest.mark.analysis
def test_repo_is_obs_lint_clean():
    from repro.core.analysis.lint import lint_metrics
    assert lint_metrics(_src_root()) == []


@pytest.mark.analysis
def test_obs001_flags_unregistered_increment():
    from repro.core.analysis.lint import lint_stats_sources
    src = "class S:\n    def f(self):\n        self.stats.bogus += 1\n"
    found = lint_stats_sources([("x.py", src)], set(REGISTERED_STATS))
    assert [f.code for f in found] == ["OBS001"]
    assert "bogus" in found[0].message and found[0].line == 3


@pytest.mark.analysis
def test_obs001_flags_dict_entry_increment():
    from repro.core.analysis.lint import lint_stats_sources
    src = ("class S:\n    def f(self, k):\n"
           "        self.stats.ghost[k] = self.stats.ghost.get(k, 0)"
           " + 1\n")
    found = lint_stats_sources([("x.py", src)], set(REGISTERED_STATS))
    assert [f.code for f in found] == ["OBS001"]
    assert "ghost" in found[0].message


@pytest.mark.analysis
def test_obs001_waiver_and_registered_fields_pass():
    from repro.core.analysis.lint import lint_stats_sources
    src = ("class S:\n    def f(self):\n"
           "        self.stats.compiles += 1\n"
           "        self.stats.secret += 1  # lint: allow(OBS001)\n"
           "        self.other.thing += 1\n")
    found = lint_stats_sources([("x.py", src)], set(REGISTERED_STATS))
    assert found == []


@pytest.mark.analysis
def test_obs002_flags_stale_registration(tmp_path):
    from repro.core.analysis.lint import lint_metrics
    core = tmp_path / "repro" / "core"
    (core / "obs").mkdir(parents=True)
    (core / "serving").mkdir()
    (core / "obs" / "metrics.py").write_text(
        'REGISTERED_STATS = {"compiles": "compiles_total", '
        '"phantom": "phantom_total"}\n')
    (core / "service.py").write_text(
        "class ServiceStats:\n    compiles: int = 0\n")
    (core / "serving" / "scheduler.py").write_text(
        "class RuntimeStats:\n    pass\n")
    found = lint_metrics(str(tmp_path))
    assert [f.code for f in found] == ["OBS002"]
    assert "phantom" in found[0].message


# ---------------------------------------------------------------------------
# the served path's stages: spans on the profiler clock, and counters
# ---------------------------------------------------------------------------

STAGES = ["prepare", "bind", "launch", "wait", "fetch"]


def _q4(dtype: str) -> str:
    """Q4 with another data type: a new text (a prepare-memo miss)
    of the same template (a plan-cache hit)."""
    return ALL["Q4"].replace('"TMAX"', f'"{dtype}"')


def test_execute_stages_nest_in_the_tracer(weather_db_small):
    tr = Tracer()
    svc = QueryService(weather_db_small, tracer=tr)
    svc.execute(ALL["Q4"]).rows()          # warm: compile outside
    tr.clear()
    rs = svc.execute(_q4("TMIN"))
    rs.rows()                              # outside the service
    top = tr.records[0]
    assert top.name == "execute" and top.parent is None
    assert [s.name for s in tr.records
            if s.parent == top.sid] == STAGES
    assert "verify" in [s.name for s in tr.records]    # in prepare
    assert tr.records[-1].name == "decode"
    assert tr.records[-1].parent is None
    assert top.args["sig"] == svc.prepare(_q4("TMIN")).digest


def test_spans_mirror_into_the_profiler(weather_db_small, tmp_path):
    """An enabled tracer's spans land in the profiler's trace as
    ``vxq.<span>`` annotations: ``execute`` holds the stages in order,
    and ``decode`` follows it."""
    import jax
    from jax.profiler import ProfileData
    svc = QueryService(weather_db_small, tracer=Tracer())
    svc.execute(ALL["Q4"]).rows()
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.execute(_q4("PRCP")).rows()
    finally:
        jax.profiler.stop_trace()
    paths = list(tmp_path.glob("**/*.xplane.pb"))
    assert len(paths) == 1
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(str(paths[0])).planes
             if plane.name == "/host:CPU"
             for line in plane.lines if line.name == "python"
             for e in line.events if e.name.startswith("vxq.")]
    by = {}
    for name, a, b in spans:
        by.setdefault(name[len("vxq."):], []).append((a, b))
    (ea, eb), = by["execute"]
    at = ea
    for stage in STAGES:
        (a, b), = by[stage]
        assert ea <= a and b <= eb, stage
        assert a >= at, stage              # in order
        at = b
    (da, db), = by["decode"]
    assert da >= eb


def test_null_tracer_execute_is_one_device_get(weather_db_small,
                                               monkeypatch):
    """Tracing off, ``execute`` records nothing and makes the calls it
    always made: one ``device_get`` and no ``block_until_ready``."""
    import jax
    svc = QueryService(weather_db_small)
    assert svc.tracer is NULL_TRACER
    svc.execute(ALL["Q4"]).rows()
    calls = []
    get, wait = jax.device_get, jax.block_until_ready
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append("get") or get(x))
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append("wait") or wait(x))
    svc.execute(_q4("TMIN")).rows()
    assert calls == ["get"]
    assert NULL_TRACER.records == []


def test_stage_counters_match_hand_counts(weather_db_small):
    svc = QueryService(weather_db_small)
    snap = svc.stats.snapshot()
    rs = svc.execute(ALL["Q4"])
    rows = rs.rows()
    svc.execute(ALL["Q4"])                 # memo hit: no prepare
    rs3 = svc.execute(_q4("TMIN"))
    d = svc.stats.diff(snap)
    assert d.prepares == 2
    assert d.rows_decoded == len(rows) == 1
    fetched = [sum(np.asarray(x).nbytes for x in jax.tree.leaves(r.raw))
               for r in (rs, rs3)]
    assert rs.fetch_bytes == fetched[0] > 0
    # three runs of one plan: the same tiles each time
    assert d.fetch_bytes == 3 * fetched[0] == 3 * fetched[1]
    # Q4 is max(...): one pmax, an all_gather of one f32 over 2
    # partitions, 4 bytes to the one other partition, per run
    assert d.exchange_bytes == 3 * 4


def test_exchange_bytes_per_partition_count():
    from repro.data.weather import WeatherSpec, build_database
    spec = WeatherSpec(num_stations=5, years=(1976, 2000),
                       days_per_year=2)
    got = {}
    for parts in (1, 4):
        svc = QueryService(build_database(spec, num_partitions=parts))
        svc.execute(ALL["Q5"]).rows()
        got[parts] = svc.stats.exchange_bytes
        (cp,) = svc.cached_plans()
        assert cp.exchange_bytes == got[parts]
    assert got[1] == 0
    assert got[4] > 0


def test_rows_decoded_counts_a_result_once(weather_db_small):
    svc = QueryService(weather_db_small)
    rs = svc.execute(ALL["Q4"])
    snap = svc.stats.snapshot()
    first = rs.rows()
    assert rs.rows() == first               # decoding again counts nothing
    assert svc.stats.diff(snap).rows_decoded == len(first) == 1


def test_batched_run_counts_each_request_exchange(weather_db_small):
    svc = QueryService(weather_db_small)
    svc.execute(ALL["Q4"])
    one = svc.stats.exchange_bytes
    snap = svc.stats.snapshot()
    rss = svc.execute_batch([ALL["Q4"], _q4("TMIN"), _q4("PRCP")])
    d = svc.stats.diff(snap)
    assert d.batches == 1
    assert d.exchange_bytes == 4 * one       # bucket of 4, one run
    assert d.fetch_bytes == rss[0].fetch_bytes > 0
    assert sum(len(rs.rows()) for rs in rss) == 3
    assert svc.stats.diff(snap).rows_decoded == 3


def test_persisted_plan_keeps_exchange_bytes(weather_db_small, tmp_path):
    svc = QueryService(weather_db_small, persist_dir=str(tmp_path))
    svc.execute(ALL["Q4"])
    fresh = QueryService(weather_db_small, persist_dir=str(tmp_path))
    fresh.execute(ALL["Q4"])
    assert fresh.stats.compiles == 0 and fresh.stats.persist_hits == 1
    assert fresh.stats.exchange_bytes == svc.stats.exchange_bytes == 4
