"""``run``: it refuses a machine without a TPU, with no fallback; and a
run driven on the CPU with its timed path broken underneath reads
``correct`` false, once for each fault a cell can have."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench import run, spec
from repro.core import executor

BENCH = spec.benchmark()


def _cli(env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "ghcnd-1m.scan",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_off_tpu():
    p = _cli({})
    assert p.returncode == 2 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_a_kernel_override():
    p = _cli({"REPRO_FORCE_JNP": "1"})
    assert p.returncode == 2 and p.stdout == ""


def _mix():
    """Three templates of the cells' mixes: a join returning rows, a
    grouping, and a scalar."""
    ts = {t["name"]: t for m in ("analytic", "scan")
          for t in spec.traffic(m)["templates"]}
    return {"loop": "closed", "deck": 4,
            "templates": [ts["Q6"], ts["Q9"], ts["Q3"]]}


def _run(cfg):
    cell = spec.cell(BENCH, "ghcnd-1m.analytic")
    return run.run_cell(BENCH, cell, cfg, _mix(), spec.limits(cell["name"]),
                        2**31 + 99, 1.0, False, jax.devices()[:1])


def _altered_rows(orig):
    def rows(self):
        out = orig(self)
        if out:
            first = list(out[0])
            v = first[-1]
            first[-1] = v + 1.0 if isinstance(v, float) else f"{v}0"
            out[0] = tuple(first)
        return out
    return rows


def _half_partitions(orig):
    def init(self, db, plan, raw, schema, profile_meta=None):
        raw = dict(raw)
        valid = np.array(raw["valid"])
        valid[valid.shape[0] // 2:] = False
        raw["valid"] = valid
        orig(self, db, plan, raw, schema, profile_meta)
    return init


def _no_exchange(keys, valid, cols, comm, dest):
    return keys, valid, cols


def test_sound_run_is_correct(cfg):
    out = _run(cfg)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 3 and out["failed"] == 0
    assert set(out["metrics"]) == {"query_p50_s", "query_p95_s",
                                   "queries_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["answer_altered", "half_partitions",
                                   "exchange_left_out"])
def test_fault_reads_not_correct(cfg, monkeypatch, fault):
    RS = executor.ResultSet
    if fault == "answer_altered":
        monkeypatch.setattr(RS, "rows", _altered_rows(RS.rows))
    elif fault == "half_partitions":
        monkeypatch.setattr(RS, "__init__", _half_partitions(RS.__init__))
    else:
        monkeypatch.setattr(executor, "_exchange", _no_exchange)
    out = _run(cfg)
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] > 0
