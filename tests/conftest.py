"""Shared fixtures. NOTE: no XLA_FLAGS here — tests and benches must
see 1 device; tests/test_distributed.py spawns subprocesses that force
their own host device counts."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

from repro.data.weather import WeatherSpec, build_database


def canon(rows):
    return sorted(map(str, rows))


def assert_grouped_rows(got_rows, want_rows, rel=1e-5):
    """Grouped results: exact on the key column, allclose on the
    aggregate columns (device f32 vs the host oracle's f64)."""
    got = sorted(got_rows)
    want = sorted(want_rows)
    assert len(got) == len(want), (len(got), len(want))
    for g, w in zip(got, want):
        assert g[0] == w[0], (g, w)
        np.testing.assert_allclose(
            [float(x) for x in g[1:]], [float(x) for x in w[1:]],
            rtol=rel)


def check_result(rs, oracle, name, rel=1e-3, grouped_rel=1e-5):
    """One result checker for all query classes: scalar queries
    compare approximately, grouped queries key-exact/aggregate-close,
    row queries canonical-exact."""
    from repro.core.queries import GROUPED, SCALAR
    if name in SCALAR:
        assert rs.scalar() == pytest.approx(oracle[name], rel=rel)
    elif name in GROUPED:
        assert_grouped_rows(rs.rows(), oracle[name], rel=grouped_rel)
    else:
        assert canon(rs.rows()) == oracle[name]


WEATHER_SPEC = WeatherSpec(num_stations=8,
                           years=(1976, 1999, 2000, 2001, 2003, 2004),
                           days_per_year=3)


@pytest.fixture(scope="session")
def weather_db():
    return build_database(WEATHER_SPEC, num_partitions=4)


@pytest.fixture(scope="session")
def oracle(weather_db):
    """SaxonLike tree-walker results for every query in queries.ALL —
    the differential-testing ground truth, computed once per session.
    Grouped queries keep raw (key, aggregates...) row tuples so the
    checker can compare aggregates approximately."""
    from repro.core.baselines import SaxonLike
    from repro.core.queries import ALL, GROUPED, SCALAR
    sx = SaxonLike(weather_db)
    out = {}
    for name, q in ALL.items():
        if name in SCALAR:
            out[name] = sx.run(q)[0]
        elif name in GROUPED:
            out[name] = sorted(sx.run_rows(q))
        else:
            out[name] = canon(sx.run_rows(q))
    return out


@pytest.fixture(scope="session")
def weather_db_small():
    spec = WeatherSpec(num_stations=5, years=(1976, 2000),
                       days_per_year=2)
    return build_database(spec, num_partitions=2)
