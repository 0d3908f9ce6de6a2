"""The control of ``correct``: the reference in the program's place,
computed in bfloat16, the precision below the configuration's float32.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --requests 120

For each seed it draws the cell's archive at full size and the first
``--requests`` requests of the window's stream, answers them with the
control, and prints the numbers the benchmark compares (wrong answers,
widest relative gap) beside the cell's limits, one JSON line per seed.
A sound limit lies below what the control reads. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import data, reference, spec, traffic  # noqa: E402


def readings(cfg: dict, mix: dict, seed: int, requests: int) -> dict:
    rec = data.records(cfg, seed)
    ref = reference.Reference(cfg, rec)
    ctl = reference.Reference(cfg, rec, precision="bfloat16")
    wrong, rel = 0, 0.0
    for r in itertools.islice(iter(traffic.Generator(mix, cfg, seed)),
                              requests):
        exact, g = reference.compare(r.name, ctl.answer(r.name, r.args),
                                     ref.answer(r.name, r.args))
        wrong += not exact
        rel = max(rel, g)
    return {"wrong_answers": wrong, "max_rel_err": rel}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    limits = spec.limits(cell["name"])
    for seed in (int(s) for s in args.seeds.split(",")):
        got = readings(cfg, mix, seed, args.requests)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control": got, "limits": limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
