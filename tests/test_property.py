"""Property-based tests (hypothesis) on system invariants:

  * optimized plan ≡ unoptimized semantics: for randomized weather
    datasets and randomized filter predicates, the fused SPMD executor
    matches the tree-walking interpreter;
  * rewrite engine: fixpoint termination, variable hygiene (no var
    defined twice, every used var defined);
  * kernels: segmented reduction and join vs oracles on random inputs;
  * partition invariance: results are independent of the partition
    count (the paper's scale-up property, in miniature).
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings, strategies as st

import jax.numpy as jnp

from repro.core import Executor, compile_query
from repro.core.algebra import defined_vars, free_vars, used_exprs, walk
from repro.core.baselines import SaxonLike
from repro.core.queries import ALL
from repro.core.translator import translate
from repro.core.rewrite import optimize
from repro.data.weather import WeatherSpec, build_database
from repro.kernels import ref

SETTLE = settings(deadline=None, max_examples=8,
                  suppress_health_check=list(HealthCheck))


@st.composite
def weather_specs(draw):
    return WeatherSpec(
        num_stations=draw(st.integers(2, 10)),
        years=tuple(draw(st.lists(st.sampled_from(
            [1976, 1999, 2000, 2001, 2003]), min_size=1, max_size=3,
            unique=True))),
        days_per_year=draw(st.integers(2, 4)))


@st.composite
def filter_queries(draw):
    dtype = draw(st.sampled_from(["TMAX", "TMIN", "PRCP", "AWND"]))
    thresh = draw(st.integers(-200, 600))
    op = draw(st.sampled_from(["gt", "lt", "ge", "le"]))
    return f'''
for $r in collection("/sensors")/dataCollection/data
where $r/dataType eq "{dtype}"
and decimal(data($r/value)) {op} {thresh}
return $r
'''


@SETTLE
@given(spec=weather_specs(), query=filter_queries(),
       parts=st.integers(1, 5))
def test_random_filters_match_saxon(spec, query, parts):
    db = build_database(spec, num_partitions=parts)
    got = sorted(map(str, Executor(db).run(
        compile_query(query)).rows()))
    want = sorted(map(str, SaxonLike(db).run_rows(query)))
    assert got == want


@SETTLE
@given(spec=weather_specs(), p1=st.integers(1, 3), p2=st.integers(4, 6))
def test_partition_invariance(spec, p1, p2):
    """Same data, different partitioning -> same Q4 answer (scale-up
    correctness)."""
    q = ALL["Q4"]
    db1 = build_database(spec, num_partitions=p1)
    db2 = build_database(spec, num_partitions=p2)
    a = Executor(db1).run(compile_query(q)).scalar()
    b = Executor(db2).run(compile_query(q)).scalar()
    assert a == pytest.approx(b, rel=1e-5)


@SETTLE
@given(qname=st.sampled_from(list(ALL)))
def test_rewrite_variable_hygiene(qname):
    plan = optimize(translate(ALL[qname]))
    defined: set[int] = set()
    for op in walk(plan):
        for v in defined_vars(op):   # GROUP-BY defines key + agg vars
            assert v not in defined, f"var {v} defined twice"
            defined.add(v)
    for op in walk(plan):
        for e in used_exprs(op):
            for v in free_vars(e):
                assert v in defined, f"var {v} used but never defined"


@SETTLE
@given(st.data())
def test_segmented_sum_property(data):
    n = data.draw(st.sampled_from([128, 256, 512]))
    s = data.draw(st.integers(1, 64))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    vals = jnp.asarray(rng.normal(size=n), jnp.float32)
    segs = jnp.asarray(rng.integers(-2, s + 2, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) > 0.3)
    sums, cnts = ref.segmented_sum_count(vals, segs, valid, s)
    # invariant: total of segment sums == masked total
    ok = np.asarray(valid) & (np.asarray(segs) >= 0) \
        & (np.asarray(segs) < s)
    np.testing.assert_allclose(float(jnp.sum(sums)),
                               float(np.asarray(vals)[ok].sum()),
                               atol=1e-3)
    assert float(jnp.sum(cnts)) == float(ok.sum())


@SETTLE
@given(st.data())
def test_join_probe_property(data):
    """Every matched probe key equals its build key; every unmatched
    valid probe key is absent from the valid build set."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    nb = data.draw(st.sampled_from([64, 128]))
    np_ = data.draw(st.sampled_from([64, 256]))
    bk = rng.choice(500, nb, replace=False).astype(np.int32)
    pk = rng.integers(0, 600, np_).astype(np.int32)
    bv = rng.random(nb) > 0.2
    pv = rng.random(np_) > 0.2
    pos, matched = ref.block_join_probe(
        (jnp.asarray(bk),), jnp.asarray(bv),
        (jnp.asarray(pk),), jnp.asarray(pv))
    pos, matched = np.asarray(pos), np.asarray(matched)
    valid_build = set(bk[bv].tolist())
    for i in range(np_):
        if matched[i]:
            assert bv[pos[i]] and pv[i]
            assert bk[pos[i]] == pk[i]
        elif pv[i]:
            assert pk[i] not in valid_build
