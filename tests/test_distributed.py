"""Multi-device SPMD tests (subprocess: 8 forced host devices — the
device count must be set before jax initializes, so these cannot run
in the main pytest process)."""
import itertools
import json
import os
import subprocess
import sys

import pytest
from conftest import assert_grouped_rows

from repro.core.baselines import SaxonLike
from repro.core.queries import ALL, GROUPED, SCALAR
from repro.data.weather import WeatherSpec, build_database

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_queries_spmd_8dev():
    print(run_py('''
import jax
from repro.core import Executor, ExecConfig, compile_query
from repro.core.baselines import SaxonLike
from repro.core.queries import ALL, SCALAR
from repro.data.weather import WeatherSpec, build_database

db = build_database(WeatherSpec(num_stations=8, years=(1976, 2000, 2001),
                                days_per_year=3), num_partitions=8)
mesh = jax.make_mesh((8,), ("data",), (jax.sharding.AxisType.Auto,))
sx = SaxonLike(db)
for name in ("Q1", "Q4", "Q5", "Q8"):
    for strat in ("broadcast", "repartition"):
        ex = Executor(db, ExecConfig(join_strategy=strat))
        rs = ex.run(compile_query(ALL[name]), mode="spmd", mesh=mesh)
        if name in SCALAR:
            want = sx.run(ALL[name])[0]
            got = rs.scalar()
            assert abs(got - want) < 1e-3 * max(1.0, abs(want)), (name, strat, got, want)
        else:
            got = sorted(map(str, rs.rows()))
            want = sorted(map(str, sx.run_rows(ALL[name])))
            assert got == want, (name, strat, len(got), len(want))
print("SPMD-8 OK")
'''))


def test_scheduled_batch_spmd_8dev():
    """Batched dispatch under shard_map on a real 8-device mesh:
    stacked parameter vectors replicated across the mesh, the batch
    vmap outside the "data" axis — one device dispatch serves B
    bindings on 8 partitions, bit-identical to per-request spmd
    execution (including through the async submit/drain runtime)."""
    print(run_py('''
import jax
from repro.core import QueryService
from repro.core.workload import variant_grid
from repro.data.weather import WeatherSpec, build_database

db = build_database(WeatherSpec(num_stations=8, years=(1976, 2000, 2001),
                                days_per_year=3), num_partitions=8)
mesh = jax.make_mesh((8,), ("data",), (jax.sharding.AxisType.Auto,))
stations = ["GHCND:USW00012836", "GHCND:USW00014771"]
years = (1976, 2000, 2001)
texts = variant_grid("Q1", stations, years, 4) + variant_grid("Q3", stations, years, 3)

svc = QueryService(db, mode="spmd", mesh=mesh)
per_req = [svc.execute(t) for t in texts]

svc_b = QueryService(db, mode="spmd", mesh=mesh)
batched = svc_b.execute_batch(texts)
assert svc_b.stats.batches == 2, svc_b.stats.batches
for a, b in zip(per_req, batched):
    assert a.rows() == b.rows()

svc_s = QueryService(db, mode="spmd", mesh=mesh)
tickets = [svc_s.submit(t, tenant="AB"[i % 2]) for i, t in enumerate(texts)]
svc_s.drain()
for a, tk in zip(per_req, tickets):
    assert tk.error is None, tk.error
    assert a.rows() == tk.result.rows()
print("SPMD-BATCH-8 OK")
'''))


SPEC_4DEV = dict(num_stations=8, years=(1976, 2000, 2001), days_per_year=3)
STRATEGIES = ("broadcast", "repartition")


@pytest.fixture(scope="module")
def spmd_4dev():
    """Every template of queries.ALL under shard_map on a 4-device
    mesh, one partition a device, with each join strategy: one child
    process, results as JSON keyed "<template>-<strategy>"."""
    out = run_py(f'''
import json
import jax
from repro.core import Executor, ExecConfig, compile_query
from repro.core.queries import ALL, GROUPED, SCALAR
from repro.data.weather import WeatherSpec, build_database

db = build_database(WeatherSpec(**{SPEC_4DEV!r}), num_partitions=4)
mesh = jax.make_mesh((4,), ("data",), (jax.sharding.AxisType.Auto,))
res = {{}}
for strat in {STRATEGIES!r}:
    ex = Executor(db, ExecConfig(join_strategy=strat))
    for name, text in ALL.items():
        rs = ex.run(compile_query(text), mode="spmd", mesh=mesh)
        if name in SCALAR:
            res[f"{{name}}-{{strat}}"] = rs.scalar()
        elif name in GROUPED:
            res[f"{{name}}-{{strat}}"] = [list(r) for r in rs.rows()]
        else:
            res[f"{{name}}-{{strat}}"] = sorted(map(str, rs.rows()))
print(json.dumps(res))
''', devices=4)
    return json.loads(out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def saxon_4dev():
    return SaxonLike(build_database(WeatherSpec(**SPEC_4DEV),
                                    num_partitions=4))


@pytest.mark.parametrize("name,strategy",
                         list(itertools.product(ALL, STRATEGIES)))
def test_spmd_4dev_matches_saxon(spmd_4dev, saxon_4dev, name, strategy):
    """The four-chip cell's execution path, per template and join
    strategy: shard_map over four devices answers as the tree-walking
    oracle does."""
    got = spmd_4dev[f"{name}-{strategy}"]
    if name in SCALAR:
        want = saxon_4dev.run(ALL[name])[0]
        assert abs(got - want) < 1e-3 * max(1.0, abs(want)), (got, want)
    elif name in GROUPED:
        assert_grouped_rows([tuple(r) for r in got],
                            saxon_4dev.run_rows(ALL[name]))
    else:
        assert got == sorted(map(str, saxon_4dev.run_rows(ALL[name])))
