"""Benchmark orchestrator: one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick]``
``PYTHONPATH=src python -m benchmarks.run --suite groupby``  (the
group-by serving workload, reproducible with one command)

CSV rows: table,name,metric,value,derived.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro import compile_cache

# named suites: shorthand for section subsets (--suite groupby ==
# --only serving_groupby)
SUITES = {
    "groupby": ["serving_groupby"],
    "ordered": ["serving_ordered"],
    "multitenant": ["serving_multitenant"],
    "obs": ["serving_obs"],
    "capacity": ["serving_capacity"],
    "serving": ["serving", "serving_groupby", "serving_ordered",
                "serving_multitenant", "serving_obs",
                "serving_capacity"],
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweeps (CI mode)")
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    ap.add_argument("--suite", default=None, choices=sorted(SUITES),
                    help="named section subset")
    args = ap.parse_args()
    compile_cache.enable()

    from benchmarks import q_benchmarks, serving_benchmarks

    sections = {
        "fig5_vs_saxon": lambda: q_benchmarks.fig5_vs_saxon(
            ("Q1", "Q4") if args.quick else
            ("Q1", "Q2", "Q3", "Q4", "Q5")),
        "fig10_vs_mrql": lambda: q_benchmarks.fig10_vs_mrql(
            ("Q4", "Q8") if args.quick else
            ("Q1", "Q3", "Q4", "Q5", "Q8")),
        "fig56_speedup": lambda: q_benchmarks.fig56_speedup(
            ("Q4",) if args.quick else ("Q2", "Q4"),
            (1, 4) if args.quick else (1, 2, 4, 8)),
        "fig89_scaleup": lambda: q_benchmarks.fig89_scaleup(
            ("Q4",) if args.quick else ("Q2", "Q4"),
            (1, 4) if args.quick else (1, 2, 4, 8)),
        "ablation": q_benchmarks.ablation,
        "fig5_service": lambda: q_benchmarks.fig5_service(
            ("Q1", "Q4") if args.quick else
            ("Q1", "Q2", "Q3", "Q4", "Q5")),
        "fig56_service": lambda: q_benchmarks.fig56_service(
            ("Q4",) if args.quick else ("Q2", "Q4"),
            (1, 4) if args.quick else (1, 2, 4, 8)),
        "service_ablation": q_benchmarks.service_ablation,
        "serving": lambda: serving_benchmarks.serving(
            variants=8 if args.quick else 64,
            repeats=1 if args.quick else 3,
            smoke=args.quick,
            # keep the committed 64-variant record out of quick runs
            out_path=("BENCH_serving_smoke.json" if args.quick
                      else "BENCH_serving.json")),
        "serving_groupby": lambda: serving_benchmarks.serving_groupby(
            variants=8 if args.quick else 64,
            repeats=1 if args.quick else 3,
            smoke=args.quick,
            out_path=("BENCH_serving_smoke.json" if args.quick
                      else "BENCH_serving.json")),
        "serving_ordered": lambda: serving_benchmarks.serving_ordered(
            variants=8 if args.quick else 64,
            repeats=1 if args.quick else 3,
            smoke=args.quick,
            out_path=("BENCH_serving_smoke.json" if args.quick
                      else "BENCH_serving.json")),
        "serving_multitenant":
            lambda: serving_benchmarks.serving_multitenant(
                variants=8 if args.quick else 64,
                smoke=args.quick,
                out_path=("BENCH_serving_smoke.json" if args.quick
                          else "BENCH_serving.json")),
        "serving_obs": lambda: serving_benchmarks.serving_obs(
            variants=8 if args.quick else 64,
            repeats=1 if args.quick else 3,
            smoke=args.quick,
            out_path=("BENCH_serving_smoke.json" if args.quick
                      else "BENCH_serving.json")),
        "serving_capacity":
            lambda: serving_benchmarks.serving_capacity(
                variants=8 if args.quick else 64,
                smoke=args.quick),
        "ingest": q_benchmarks.ingest,
    }
    if args.suite:
        chosen = SUITES[args.suite]
    elif args.only:
        chosen = args.only.split(",")
    else:
        chosen = list(sections)
    print("table,name,metric,value,derived")
    failures = []
    for name in chosen:
        try:
            sections[name]()
        except Exception as e:
            failures.append(name)
            print(f"# SECTION FAILED {name}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"failed sections: {failures}")


if __name__ == "__main__":
    main()
