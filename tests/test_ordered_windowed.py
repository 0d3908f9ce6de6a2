"""Ordered & windowed grouped serving (ISSUE 5 tentpole): ORDER BY on
aggregate columns + LIMIT top-k pushdown end-to-end against the
tree-walking oracle, the topk_cap presize/regrowth knob bounds (tested
like ``binding_stats_capacity``), and the streaming-window grouped
mode through ``submit(..., stream=)`` / ``stream_result``."""
import jax
import numpy as np
import pytest

from repro.core import ExecConfig, Executor, QueryService, compile_query
from repro.core.algebra import Limit, OrderBy, walk
from repro.core.baselines import SaxonLike
from repro.core.queries import ALL
from repro.core.workload import q11_variant, q11c_variant, q12_variant

YEARS = (1976, 1999, 2000, 2001, 2003, 2004)


# -- plan shape --------------------------------------------------------------


def test_q11_plan_has_orderby_limit():
    plan = compile_query(ALL["Q11"])
    ops = list(walk(plan))
    obs = [o for o in ops if isinstance(o, OrderBy)]
    lims = [o for o in ops if isinstance(o, Limit)]
    assert len(obs) == 1 and len(lims) == 1
    assert lims[0].k == 3
    # user key (sum desc) + the appended grouping-key asc tiebreak
    assert [d for _, d in obs[0].keys] == [True, False]


def test_limit_without_order_rejected():
    with pytest.raises(NotImplementedError):
        compile_query('''
for $r in collection("/sensors")/dataCollection/data
group by $st := $r/station
limit 3
return ($st, count($r))
''')


def test_order_outside_groupby_rejected():
    with pytest.raises(NotImplementedError):
        compile_query('''
for $r in collection("/sensors")/dataCollection/data
order by $r/value descending
return $r
''')


# -- ordered results vs the tree-walking oracle ------------------------------


@pytest.mark.parametrize("variant,dtype", [
    (q11_variant, "TMAX"), (q11_variant, "PRCP"),
    (q11c_variant, "TMAX"),     # count-ordered: all ties, pure tiebreak
])
def test_ordered_groupby_matches_saxon_in_order(weather_db, variant,
                                                dtype):
    """Device ranking == host ranking, ROW ORDER INCLUDED (the
    grouping-key tiebreak makes the order total, so this is an exact
    list comparison, not a sorted-set one)."""
    q = variant(dtype)
    got = Executor(weather_db).run(compile_query(q)).rows()
    want = [tuple(r) for r in SaxonLike(weather_db).run_rows(q)]
    assert len(got) == 3
    assert [g[0] for g in got] == [w[0] for w in want]
    for g, w in zip(got, want):
        np.testing.assert_allclose([float(x) for x in g[1:]],
                                   [float(x) for x in w[1:]],
                                   rtol=1e-5)


def test_topk_pushdown_materializes_fewer_rows(weather_db):
    """The pushdown's point: a limit-3 query over an 8-station
    dictionary emits a ~k-wide sorted tile, not the full segment
    width — bit-identically."""
    full = Executor(weather_db).run(compile_query(ALL["Q11"]))
    pushed = Executor(weather_db, ExecConfig(topk_cap=16)).run(
        compile_query(ALL["Q11"]))
    assert not pushed.overflow
    assert pushed.rows() == full.rows()
    assert pushed.raw["valid"].shape[-1] == 16
    assert full.raw["valid"].shape[-1] > 16     # full dictionary width


def test_spmd_ordered_topk_matches_sim():
    """The capacity-bounded segmented sort lowers under shard_map too:
    spmd Q11 with a bounded topk_cap equals the sim-mode full-width
    run bitwise (cf. test_spmd_grouped_capped_segments)."""
    from repro.data.weather import WeatherSpec, build_database
    mesh = jax.make_mesh((1,), ("data",), (jax.sharding.AxisType.Auto,))
    db1 = build_database(WeatherSpec(num_stations=5,
                                     years=(1976, 2000),
                                     days_per_year=2),
                         num_partitions=1)
    want = Executor(db1).run(compile_query(ALL["Q11"])).rows()
    rs = Executor(db1, ExecConfig(topk_cap=16)).run(
        compile_query(ALL["Q11"]), mode="spmd", mesh=mesh)
    assert not rs.overflow
    assert rs.rows() == want


# -- topk_cap knob bounds (the binding_stats_capacity treatment) -------------


def test_topk_cap_presized_not_floor(weather_db):
    """The service's first-shot topk_cap comes from statistics
    (min(round_cap(limit k), distinct-key bound)) — tiny-cap regrowth
    ladders are for mis-seeded services, not the presized path, which
    must serve Q11 with zero retries."""
    svc = QueryService(weather_db)
    rs = svc.execute(ALL["Q11"])
    assert not rs.overflow
    assert svc.stats.retries == 0
    tcaps = [c.topk_cap for c in svc.cached_configs()]
    assert tcaps and all(t == 16 for t in tcaps)    # round_cap(3)


def test_topk_cap_regrows_to_exact_and_only_topk(weather_db):
    """A mis-seeded tiny topk_cap overflows on its own flag and
    regrows alone — scan/group/join caps untouched — to the exact
    presized result."""
    rs0 = Executor(weather_db, ExecConfig(topk_cap=2)).run(
        compile_query(ALL["Q11"]))
    assert rs0.overflow and rs0.overflow_topk_cap
    assert not (rs0.overflow_scan or rs0.overflow_group_cap
                or rs0.overflow_join_cap)

    svc = QueryService(weather_db, ExecConfig(topk_cap=2))
    want = QueryService(weather_db).execute(ALL["Q11"]).rows()
    got = svc.execute(ALL["Q11"])
    assert not got.overflow
    assert got.rows() == want
    assert svc.stats.retries >= 1
    tcaps = {c.topk_cap for c in svc.cached_configs()}
    assert 2 in tcaps and max(tcaps) > 2
    # only the saturated rung grew: one group_cap across the ladder
    assert len({c.group_cap for c in svc.cached_configs()}) == 1


def test_topk_cap_ceiling_is_dictionary(weather_db):
    """The ladder's ceiling: at the full dictionary width the sorted
    tile clips to its child's width and overflow is impossible by
    construction — the regrowth termination proof for this rung."""
    cap = len(weather_db.strings)
    rs = Executor(weather_db, ExecConfig(topk_cap=cap)).run(
        compile_query(ALL["Q11"]))
    assert not rs.overflow_topk_cap


def test_pushdown_knob_off_keeps_full_sort(weather_db):
    """pushdown_topk=False is the full-sort-then-slice ablation: no
    topk_cap is presized, results stay bit-identical."""
    push = QueryService(weather_db)
    full = QueryService(weather_db, pushdown_topk=False)
    a, b = push.execute(ALL["Q11"]), full.execute(ALL["Q11"])
    assert a.rows() == b.rows()
    assert all(c.topk_cap is None for c in full.cached_configs())
    assert any(c.topk_cap is not None for c in push.cached_configs())
    # the pushdown tile is never wider than the full sort's (strictly
    # narrower once distinct keys outgrow one round_cap bucket — the
    # "ordered" benchmark's 30-station gate)
    assert a.raw["valid"].shape[-1] <= b.raw["valid"].shape[-1]


def test_join_cap_presized_from_scan_statistics(weather_db):
    """The carried-but-unused join_cap estimate, wired in: a default
    service presizes the compacted probe output from the same scan
    statistics instead of leaving it unbounded, without changing
    results or compile counts."""
    svc = QueryService(weather_db)
    rs = svc.execute(ALL["Q6"])
    assert not rs.overflow
    assert svc.stats.retries == 0
    cfgs = svc.cached_configs()
    assert all(c.join_cap is not None for c in cfgs)
    assert all(c.join_cap >= c.scan_cap for c in cfgs)
    want = Executor(weather_db).run(compile_query(ALL["Q6"])).rows()
    assert rs.rows() == want


# -- streaming-window grouped mode -------------------------------------------


def test_windowed_stream_matches_one_shot(weather_db):
    """Per-year Q12 slices submitted as stream windows across several
    admission windows and tenants merge — whatever the dispatch
    order — into the one-shot grouped result over all years, bit for
    bit (f32-exact integer data)."""
    svc = QueryService(weather_db)
    for i, y in enumerate(YEARS):
        svc.submit(q12_variant("PRCP", y), tenant="AB"[i % 2],
                   at=float(i), stream="prcp")
    tickets = svc.drain()
    assert all(t.error is None for t in tickets)
    merged = svc.stream_result("prcp")
    one_shot = sorted(svc.execute('''
for $r in collection("/sensors")/dataCollection/data
where $r/dataType eq "PRCP"
group by $st := $r/station
return ($st, count($r), sum($r/value), min($r/value), max($r/value))
''').rows())
    assert merged == one_shot


def test_windowed_stream_rejects_non_mergeable_at_submit(weather_db):
    svc = QueryService(weather_db)
    with pytest.raises(ValueError):
        svc.submit(ALL["Q9"], stream="bad")     # avg: not mergeable
    # the failed submit must not leave a half-open stream
    with pytest.raises(KeyError):
        svc.stream_result("bad")


def test_windowed_stream_refuses_after_lost_window(weather_db):
    """A streamed ticket that errors at dispatch poisons the stream:
    totals missing a window are wrong, not partial, so stream_result
    must raise instead of returning them."""
    svc = QueryService(weather_db, ExecConfig(group_cap=2),
                       presize=False, max_retries=0)
    t = svc.submit(q12_variant("PRCP", YEARS[0]), at=0.0, stream="s")
    svc.drain()
    assert t.error is not None      # group_cap=2 cannot serve 8 keys
    with pytest.raises(RuntimeError, match="lost window"):
        svc.stream_result("s")


def test_windowed_stream_survives_drain(weather_db):
    """Streams accumulate across admission horizons: windows absorbed
    after a drain keep merging into the same state."""
    svc = QueryService(weather_db)
    svc.submit(q12_variant("PRCP", YEARS[0]), at=0.0, stream="s")
    svc.drain()
    first = svc.stream_result("s")
    svc.submit(q12_variant("PRCP", YEARS[1]), at=10.0, stream="s")
    svc.drain()
    second = svc.stream_result("s")
    assert len(second) >= len(first)
    counts_first = {r[0]: r[1] for r in first}
    counts_second = {r[0]: r[1] for r in second}
    assert all(counts_second[k] >= v for k, v in counts_first.items())
