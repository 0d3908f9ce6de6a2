"""XQuery 3.0 group-by (the paper's §6 'planned next step', built as a
beyond-paper feature on the keyed two-step aggregation path)."""
import numpy as np
import pytest
from conftest import check_result

from repro.core import ExecConfig, Executor, QueryService, compile_query
from repro.core.algebra import GroupBy, walk
from repro.core.baselines import SaxonLike
from repro.core.executor import SEG_COMPARE_CAP_MAX
from repro.core.queries import ALL, GROUPED
from repro.data.weather import WeatherSpec, build_database

GB_QUERY = '''
for $r in collection("/sensors")/dataCollection/data
where $r/dataType eq "TMAX"
group by $st := $r/station
return ($st, count($r), sum($r/value), max($r/value))
'''

AVG_QUERY = '''
for $r in collection("/sensors")/dataCollection/data
where $r/dataType eq "PRCP"
group by $st := $r/station
return ($st, avg($r/value))
'''


def expected_groups(db, dtype, fns):
    """Hand-rolled oracle over the flat (station, value) pairs from the
    Saxon-style walker."""
    sx = SaxonLike(db)
    flat = sx.run_rows(f'''
for $r in collection("/sensors")/dataCollection/data
where $r/dataType eq "{dtype}"
return ($r/station, $r/value)
''')
    groups: dict[str, list[float]] = {}
    for st, v in flat:
        groups.setdefault(st, []).append(float(v))
    out = {}
    for st, vs in groups.items():
        row = []
        for fn in fns:
            row.append({"count": float(len(vs)), "sum": sum(vs),
                        "max": max(vs), "min": min(vs),
                        "avg": sum(vs) / len(vs)}[fn])
        out[st] = tuple(row)
    return out


def test_groupby_plan_has_operator(weather_db):
    plan = compile_query(GB_QUERY)
    gbs = [o for o in walk(plan) if isinstance(o, GroupBy)]
    assert len(gbs) == 1
    assert [fn for _, fn, _ in gbs[0].aggs] == ["count", "sum", "max"]


@pytest.mark.parametrize("pallas", [False, True])
def test_groupby_count_sum_max(weather_db, pallas):
    ex = Executor(weather_db, ExecConfig(use_pallas_join=pallas))
    rows = ex.run(compile_query(GB_QUERY)).rows()
    want = expected_groups(weather_db, "TMAX", ("count", "sum", "max"))
    got = {st: (c, s, m) for st, c, s, m in rows}
    assert set(got) == set(want)
    for st in want:
        np.testing.assert_allclose(got[st], want[st], rtol=1e-5)


def test_groupby_avg(weather_db):
    ex = Executor(weather_db)
    rows = ex.run(compile_query(AVG_QUERY)).rows()
    want = expected_groups(weather_db, "PRCP", ("avg",))
    got = {st: (a,) for st, a in rows}
    assert set(got) == set(want)
    for st in want:
        np.testing.assert_allclose(got[st], want[st], rtol=1e-5)


HAVING_QUERY = '''
for $r in collection("/sensors")/dataCollection/data
where $r/dataType eq "TMAX"
group by $st := $r/station
where count($r) ge 2 and max($r/value) gt 200
return ($st, count($r), max($r/value))
'''


def test_groupby_having_filters_groups(weather_db):
    """HAVING-style where-after-group-by: groups failing the post-
    aggregation predicate are dropped, surviving groups keep exact
    aggregates."""
    ex = Executor(weather_db)
    rows = ex.run(compile_query(HAVING_QUERY)).rows()
    want = expected_groups(weather_db, "TMAX", ("count", "max"))
    kept = {st: v for st, v in want.items()
            if v[0] >= 2 and v[1] > 200}
    got = {st: (c, m) for st, c, m in rows}
    assert set(got) == set(kept)
    for st in kept:
        np.testing.assert_allclose(got[st], kept[st], rtol=1e-5)


def test_groupby_having_plan_shape():
    """The post-filter lowers to SELECT above GROUP-BY, sharing one
    aggregate slot per distinct (fn, arg) between HAVING and return."""
    from repro.core.algebra import Select
    plan = compile_query(HAVING_QUERY)
    ops = list(walk(plan))
    gbs = [o for o in ops if isinstance(o, GroupBy)]
    assert len(gbs) == 1
    # count/max appear once each even though HAVING and return both
    # use them
    assert sorted(fn for _, fn, _ in gbs[0].aggs) == ["count", "max"]
    assert any(isinstance(o, Select) for o in ops)


@pytest.mark.parametrize("cap", [2, 8, 16])
def test_groupby_capped_segments_exact_or_flagged(weather_db, cap):
    """group_cap below the distinct-key count must flag overflow
    (never silently truncate); at or above it, results are bit-
    identical to the full-dictionary layout."""
    full = Executor(weather_db).run(compile_query(GB_QUERY))
    capped = Executor(weather_db,
                      ExecConfig(group_cap=cap)).run(
        compile_query(GB_QUERY))
    distinct = len(full.rows())
    if cap < distinct:
        assert capped.overflow and capped.overflow_group_cap
    else:
        assert not capped.overflow
        assert capped.rows() == full.rows()


def test_groupby_capped_pallas_parity(weather_db):
    """The Pallas segmented-reduce path agrees with the jnp reference
    on the capped segment layout."""
    ref = Executor(weather_db,
                   ExecConfig(group_cap=16)).run(compile_query(GB_QUERY))
    pal = Executor(weather_db,
                   ExecConfig(group_cap=16, use_pallas_join=True)).run(
        compile_query(GB_QUERY))
    assert pal.rows() == ref.rows()


def test_groupby_minmax_skip_nonnumeric_values():
    """A non-numeric value text atomizes to NaN: excluded from every
    aggregate value (count still counts the row) — min/max must not
    see it as 0.0."""
    from repro.core import xdm
    db = xdm.Database()
    sh = xdm.Shredder(db.names, db.strings)
    doc = sh.begin_document()
    root = sh.element("dataCollection", doc)
    for st, vals in (("A", ("5", "n/a")), ("B", ("-3", "n/a"))):
        for v in vals:
            d = sh.element("data", root)
            sh.element("station", d, st)
            sh.element("dataType", d, "TMAX")
            sh.element("value", d, v)
    sh.end_document()
    db.add_collection("/sensors", [sh.finish()])
    rows = Executor(db).run(compile_query(GB_QUERY)).rows()
    got = {r[0]: r[1:] for r in rows}
    assert got["A"] == (2.0, 5.0, 5.0)
    # all-negative group: a NaN->0.0 leak would report max 0.0
    assert got["B"] == (2.0, -3.0, -3.0)


def test_groupby_partition_invariance():
    spec = WeatherSpec(num_stations=6, years=(2000, 2001),
                       days_per_year=3)
    results = []
    for p in (1, 3):
        db = build_database(spec, num_partitions=p)
        rows = Executor(db).run(compile_query(GB_QUERY)).rows()
        results.append(sorted((r[0], round(r[1], 3), round(r[2], 2))
                              for r in rows))
    assert results[0] == results[1]


# Two centuries of dates at eight readings a year intern more strings
# than the largest cap below, so every cap takes the capped segment
# space (cap < dictionary) instead of one slot per string.
WIDE_DICT_SPEC = WeatherSpec(num_stations=6, years=tuple(range(1740, 2010)),
                             days_per_year=8, datatypes=("TMAX", "PRCP"))


@pytest.fixture(scope="module")
def wide_dict():
    """(db, oracle): a database whose string dictionary exceeds 2048,
    and the SaxonLike rows of each grouped template on it."""
    db = build_database(WIDE_DICT_SPEC, num_partitions=4)
    sx = SaxonLike(db)
    return db, {n: sorted(sx.run_rows(ALL[n])) for n in GROUPED}


@pytest.mark.parametrize("cap", [SEG_COMPARE_CAP_MAX,
                                 SEG_COMPARE_CAP_MAX + 1, 512, 2048])
@pytest.mark.parametrize("name", GROUPED)
def test_segment_mapping_compare_and_search(wide_dict, name, cap):
    """The fused group-by maps rows to segments by dense compare up to
    SEG_COMPARE_CAP_MAX and by searchsorted above it; both branches,
    at the caps the benchmark cells' plans use (512 slots on one chip,
    2048 on four), serve exact rows at the requested cap."""
    db, oracle = wide_dict
    assert cap < len(db.strings)
    svc = QueryService(db, ExecConfig(group_cap=cap))
    rs = svc.execute(ALL[name])
    assert not rs.overflow
    assert svc.stats.retries == 0
    assert [(c.group_cap, c.use_pallas_segments)
            for c in svc.cached_configs()] == [(cap, True)]
    check_result(rs, oracle, name)
