"""The four-chip cell's path on the CPU: ``run.run_cell`` in spmd mode
over four virtual devices, one partition a device, on a cut of
``ghcnd-4m-x4`` whose 600 stations take the group-by past 512 slots
(the searched segment mapping). The analytic mix reads ``correct``;
with the timed path broken underneath (an answer altered, half the
partitions dropped, the join's exchange left out) it reads not
correct. The device count is fixed before JAX starts, so the runs go
in one child process."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import spec

FAULTS = ("answer_altered", "half_partitions", "exchange_left_out")

CHILD = r'''
import json, sys
import jax
from chipbench import run, spec
from chipbench.tests import test_bench_run as T
from repro.core import executor

bench = spec.benchmark()
cell = spec.cell(bench, "ghcnd-4m-x4.analytic")
cfg = spec.config(bench, cell["config"])
cfg.update(num_stations=600, years=[2000, 2001], days_per_year=2)
analytic = spec.traffic(cell["traffic"])
ts = {t["name"]: t for t in analytic["templates"]}
# a join that broadcasts, a join that repartitions, a grouping
small = {"loop": "closed", "deck": 3,
         "templates": [ts["Q6"], ts["Q8"], ts["Q9"]]}
devices = jax.devices()[:cell["chips"]]
assert len(devices) == 4, devices


def go(mix, seconds):
    out = run.run_cell(bench, cell, cfg, mix, spec.limits(cell["name"]),
                       2**31 + 4099, seconds, False, devices)
    return {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "checks": out["checks"],
            "metrics": sorted(out["metrics"]),
            "device_count": out["device"]["count"]}


RS = executor.ResultSet
res = {"sound": go(analytic, 2.0)}
for fault in sys.argv[1:]:
    saved = RS.rows, RS.__init__, executor._exchange
    if fault == "answer_altered":
        RS.rows = T._altered_rows(RS.rows)
    elif fault == "half_partitions":
        RS.__init__ = T._half_partitions(RS.__init__)
    else:
        executor._exchange = T._no_exchange
    try:
        res[fault] = go(small, 0.5)
    finally:
        RS.rows, RS.__init__, executor._exchange = saved
print(json.dumps(res))
'''


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(spec.ROOT, "src"),
                                           spec.ROOT]))
    for var in ("REPRO_FORCE_JNP", "REPRO_KERNEL_INTERPRET"):
        env.pop(var, None)
    p = subprocess.run([sys.executable, "-c", CHILD, *FAULTS],
                       cwd=spec.ROOT, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_spmd_analytic_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"], out["checks"]
    assert out["device_count"] == 4
    assert out["attempted"] >= 8 and out["failed"] == 0
    assert out["checks"]["wrong_answers"]["value"] == 0
    assert out["metrics"] == sorted(["query_p50_s", "query_p95_s",
                                     "queries_per_s", "setup_s"])


@pytest.mark.parametrize("fault", FAULTS)
def test_spmd_fault_reads_not_correct(runs, fault):
    out = runs[fault]
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] > 0
