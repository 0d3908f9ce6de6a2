"""Q1-Q10 end-to-end differential: fused SPMD executor (both join
strategies, jnp + Pallas probes) vs the MRQL-style staged baseline vs
the Saxon-style tree walker (§5.2; Q9/Q10 are the §6 group-by
shapes)."""
import jax
import numpy as np
import pytest
from conftest import canon, check_result

from repro.core import ExecConfig, Executor, compile_query
from repro.core.baselines import MrqlLike, SaxonLike
from repro.core.queries import ALL, SCALAR


@pytest.mark.parametrize("name", list(ALL))
def test_executor_broadcast(weather_db, oracle, name):
    ex = Executor(weather_db)
    rs = ex.run(compile_query(ALL[name]))
    assert not rs.overflow
    check_result(rs, oracle, name)


@pytest.mark.parametrize("name", list(ALL))
def test_executor_repartition(weather_db, oracle, name):
    """Repartition-vs-broadcast parity across all paper queries
    (join-free plans must be unaffected by the strategy flag)."""
    ex = Executor(weather_db, ExecConfig(join_strategy="repartition"))
    rs = ex.run(compile_query(ALL[name]))
    assert not rs.overflow
    check_result(rs, oracle, name)


@pytest.mark.parametrize("name", ["Q5", "Q8", "Q9"])
def test_executor_pallas_join(weather_db, oracle, name):
    ex = Executor(weather_db, ExecConfig(use_pallas_join=True))
    rs = ex.run(compile_query(ALL[name]))
    check_result(rs, oracle, name)


@pytest.mark.parametrize("name", list(ALL))
def test_mrql_like(weather_db, oracle, name):
    mr = MrqlLike(weather_db)
    res = mr.run(compile_query(ALL[name]))
    check_result(res, oracle, name)
    assert res.jobs >= 1


def test_q1_returns_key_west_xmas(weather_db):
    ex = Executor(weather_db)
    rows = ex.run(compile_query(ALL["Q1"])).rows()
    assert rows, "Q1 must be non-degenerate"
    for (fp,) in rows:
        assert "GHCND:USW00012836" in fp
        assert "-12-25" in fp


def test_q2_wind_threshold(weather_db):
    ex = Executor(weather_db)
    rows = ex.run(compile_query(ALL["Q2"])).rows()
    for (fp,) in rows:
        assert "AWND" in fp
        val = float(fp.split("|")[-1])
        assert val > 491.744


def test_q6_row_arity(weather_db):
    ex = Executor(weather_db)
    rows = ex.run(compile_query(ALL["Q6"])).rows()
    assert rows and all(len(r) == 3 for r in rows)
    # station displayName | date string | value
    assert any("AIRPORT" in r[0] for r in rows)


def test_scan_capacity_overflow_flag(weather_db):
    """The Hyracks frame-size analogue: too-small capacity must raise
    the overflow flag, not silently truncate."""
    ex = Executor(weather_db, ExecConfig(scan_cap=8))
    rs = ex.run(compile_query(ALL["Q2"]))
    assert rs.overflow


def test_spmd_single_device(weather_db_small):
    """shard_map path on a 1-device mesh (the 8-device version lives in
    test_distributed.py)."""
    mesh = jax.make_mesh((1,), ("data",), (jax.sharding.AxisType.Auto,))
    from repro.data.weather import WeatherSpec, build_database
    db1 = build_database(WeatherSpec(num_stations=5, years=(1976, 2000),
                                     days_per_year=2), num_partitions=1)
    ex = Executor(db1)
    sx = SaxonLike(db1)
    rs = ex.run(compile_query(ALL["Q4"]), mode="spmd", mesh=mesh)
    assert rs.scalar() == pytest.approx(sx.run(ALL["Q4"])[0], rel=1e-3)


def test_spmd_mesh_must_match_partitions(weather_db_small):
    """One partition per device: a 1-device mesh over a 2-partition
    database raises a typed error — from the executor's spmd compile
    and from the service constructor — instead of dropping a
    partition."""
    from repro.core import InvalidArgumentError, QueryService
    mesh = jax.make_mesh((1,), ("data",), (jax.sharding.AxisType.Auto,))
    with pytest.raises(InvalidArgumentError, match="2 partitions"):
        Executor(weather_db_small).run(compile_query(ALL["Q4"]),
                                       mode="spmd", mesh=mesh)
    with pytest.raises(InvalidArgumentError):
        QueryService(weather_db_small, mode="spmd", mesh=mesh)


def test_spmd_grouped_capped_segments(weather_db_small):
    """The capped segment dictionary (all_gather + unique) lowers
    under shard_map too: spmd Q9 with a bounded group_cap equals the
    sim-mode full-dictionary run bitwise."""
    mesh = jax.make_mesh((1,), ("data",), (jax.sharding.AxisType.Auto,))
    from repro.data.weather import WeatherSpec, build_database
    db1 = build_database(WeatherSpec(num_stations=5, years=(1976, 2000),
                                     days_per_year=2), num_partitions=1)
    want = Executor(db1).run(compile_query(ALL["Q9"])).rows()
    ex = Executor(db1, ExecConfig(group_cap=16))
    rs = ex.run(compile_query(ALL["Q9"]), mode="spmd", mesh=mesh)
    assert not rs.overflow
    assert rs.rows() == want
