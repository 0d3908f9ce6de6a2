"""Multi-device SPMD tests (subprocess: 8 forced host devices — the
device count must be set before jax initializes, so these cannot run
in the main pytest process)."""
import os
import subprocess
import sys

import pytest

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


def test_sharded_train_step_8dev():
    print(run_py('''
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.launch import mesh as mesh_lib
from repro.models import model as model_lib, steps as steps_lib
from repro.optim import adamw_init

cfg = get_smoke_config("llama3-8b")
mesh = jax.make_mesh((4, 2), ("data", "model"), (jax.sharding.AxisType.Auto,) * 2)
params = model_lib.init_params(cfg, jax.random.key(0))
opt = adamw_init(params)
pspecs = mesh_lib.named(mesh, mesh_lib.param_specs(cfg, mesh))
ospecs = mesh_lib.named(mesh, mesh_lib.opt_specs(cfg, mesh, opt))
params = jax.device_put(params, pspecs)
opt = jax.device_put(opt, ospecs)
step = jax.jit(steps_lib.make_train_step(cfg, num_microbatches=2),
               in_shardings=(pspecs, ospecs, None),
               out_shardings=(pspecs, ospecs, None))
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)}
losses = []
for _ in range(4):
    params, opt, m = step(params, opt, batch)
    losses.append(float(m["loss"]))
assert all(np.isfinite(losses)), losses
assert losses[-1] < losses[0], losses
# sharded-vs-single-device equivalence
cfg1 = cfg
p1 = model_lib.init_params(cfg1, jax.random.key(0))
o1 = adamw_init(p1)
s1 = jax.jit(steps_lib.make_train_step(cfg1, num_microbatches=2))
for _ in range(4):
    p1, o1, m1 = s1(p1, o1, batch)
assert abs(float(m1["loss"]) - losses[-1]) < 1e-2, (float(m1["loss"]), losses[-1])
print("TRAIN-8 OK", losses)
'''))


def test_elastic_remesh_restore_8_to_4():
    print(run_py('''
import jax, jax.numpy as jnp, numpy as np, tempfile
from repro.checkpoint import save, restore, latest_step
from repro.configs import get_smoke_config
from repro.launch import mesh as mesh_lib
from repro.models import model as model_lib, steps as steps_lib
from repro.optim import adamw_init
from repro.runtime import ElasticState, remesh_plan
from repro.runtime.elastic import build_mesh_from_plan

cfg = get_smoke_config("qwen3-1.7b")
mesh8 = jax.make_mesh((4, 2), ("data", "model"), (jax.sharding.AxisType.Auto,) * 2)
params = model_lib.init_params(cfg, jax.random.key(0))
opt = adamw_init(params)
p8 = mesh_lib.named(mesh8, mesh_lib.param_specs(cfg, mesh8))
params = jax.device_put(params, p8)
step = jax.jit(steps_lib.make_train_step(cfg, num_microbatches=1),
               in_shardings=(p8, None, None))
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)}
params, opt, m = step(params, opt, batch)
loss8 = float(m["loss"])
d = tempfile.mkdtemp()
save(d, 1, {"params": params, "opt": opt})

# lose half the hosts -> re-mesh 4x2 -> 2x2 and restore
st = ElasticState(num_hosts=8, devices_per_host=1, model_axis=2, data_axis=4)
plan = remesh_plan(st, surviving_hosts=[0,1,2,3], global_batch=8, microbatches=1)
assert plan["mesh_shape"] == (2, 2), plan
mesh4 = build_mesh_from_plan(plan)
p4 = mesh_lib.named(mesh4, mesh_lib.param_specs(cfg, mesh4))
state = restore(d, 1, {"params": params, "opt": opt},
                {"params": p4, "opt": None})
params4 = state["params"]
step4 = jax.jit(steps_lib.make_train_step(cfg, num_microbatches=plan["microbatches"]),
                in_shardings=(p4, None, None))
params4, opt4, m4 = step4(params4, state["opt"], batch)
assert np.isfinite(float(m4["loss"]))
print("ELASTIC OK", loss8, float(m4["loss"]))
'''))


def test_dryrun_entrypoint_small():
    """The dryrun module itself (512 devices) on the cheapest cell."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "granite-moe-1b-a400m", "--shape", "decode_32k", "--mesh",
         "both", "--outdir", "/tmp/dryrun_test"],
        env=env, capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "done: 2/2 cells OK" in out.stdout, out.stdout
