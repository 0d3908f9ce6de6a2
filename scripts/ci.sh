#!/usr/bin/env bash
# Tier-1 CI loop: the ROADMAP verify command plus timing report, then
# the serving-benchmark smoke gates — scan/join, group-by, ordered
# top-k AND async multi-tenant workloads (4 variants, 1 repeat each —
# fails fast if
# prepared-query parameter sharing regresses to per-variant compiles
# or results drift from the exact path; the full 64-variant runs live
# in `python -m benchmarks.serving_benchmarks` / the slow-marked
# tests).
#
#   scripts/ci.sh                 default loop (slow-marked smokes skipped)
#   FULL=1 scripts/ci.sh          include the slow-marked full grids
#   scripts/ci.sh --differential  also run the differential-harness fast
#                                 slice as its own stage (prepared/batch/
#                                 regrowth/scheduled bit-parity across
#                                 queries.ALL)
#   scripts/ci.sh --scheduler     also run the serving-runtime smoke
#                                 stage standalone (admission/fairness/
#                                 bucketing unit+property tests plus the
#                                 4-variant multitenant benchmark gate)
#   scripts/ci.sh --properties    also run the seeded property suites
#                                 (segmented top-k vs host oracle,
#                                 windowed-merge invariance, regrowth
#                                 ladder monotonicity) as their own
#                                 stage — the fast slices; full grids
#                                 are slow-marked (FULL=1)
#   scripts/ci.sh --obs           also run the observability smoke
#                                 stage standalone (tracer/metrics/
#                                 profile unit+property tests plus the
#                                 zero-cost-when-off benchmark gate
#                                 and trace_event export validation)
#   scripts/ci.sh --capacity      also run the capacity-observatory
#                                 smoke stage standalone (flight
#                                 recorder / cost model / deviceless
#                                 simulator tests plus the tiny-trace
#                                 3-load-point sweep with its fidelity
#                                 and round-trip gates)
#   scripts/ci.sh --kernels       also run the kernel stage standalone:
#                                 the segment-engine parity suite under
#                                 REPRO_KERNEL_INTERPRET=1 (the Pallas
#                                 interpreter executes the exact TPU
#                                 kernel bodies on CPU), the kernels'
#                                 compiles for a described TPU v5e, and
#                                 the vmapped kernel-vs-jnp policy sweep
#                                 smoke
#   scripts/ci.sh --persist       also run the persistent-plan-cache
#                                 stage standalone (disk cache round
#                                 trip, restart parity, corruption and
#                                 fingerprint degradation, warmup API,
#                                 eviction counters — plus the 4-variant
#                                 cold-restart benchmark gate; the
#                                 restart suite also rides the default
#                                 loop's `--suite all` smoke pass)
#   scripts/ci.sh --lint          run ONLY the static stage: the
#                                 tracing-hazard/determinism linter
#                                 (file:line findings, nonzero exit)
#                                 plus the whole-suite plan verifier
#                                 (rewrite soundness on, presizing
#                                 cross-validated) — no test run
#   scripts/ci.sh tests/...       any extra pytest args pass through
#
# The default loop runs the linter first (seconds, catches tracing
# hazards before any compile) and the plan verifier after the tests.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
DIFFERENTIAL=0
SCHEDULER=0
PROPERTIES=0
OBS=0
KERNELS=0
CAPACITY=0
PERSIST=0
while [ "${1:-}" = "--differential" ] || [ "${1:-}" = "--scheduler" ] \
        || [ "${1:-}" = "--properties" ] || [ "${1:-}" = "--obs" ] \
        || [ "${1:-}" = "--kernels" ] || [ "${1:-}" = "--capacity" ] \
        || [ "${1:-}" = "--persist" ] || [ "${1:-}" = "--lint" ]; do
    if [ "$1" = "--differential" ]; then DIFFERENTIAL=1; fi
    if [ "$1" = "--scheduler" ]; then SCHEDULER=1; fi
    if [ "$1" = "--properties" ]; then PROPERTIES=1; fi
    if [ "$1" = "--obs" ]; then OBS=1; fi
    if [ "$1" = "--kernels" ]; then KERNELS=1; fi
    if [ "$1" = "--capacity" ]; then CAPACITY=1; fi
    if [ "$1" = "--persist" ]; then PERSIST=1; fi
    if [ "$1" = "--lint" ]; then
        python -m repro.core.analysis.lint src/repro
        python -m repro.core.analysis.verify
        exit 0
    fi
    shift
done
MARK=()
if [ "${FULL:-0}" = "1" ]; then
    MARK=(-m "slow or not slow")
fi
python -m repro.core.analysis.lint src/repro
# ${MARK[@]+...} keeps set -u happy on bash < 4.4 when MARK is empty
python -m pytest -x -q --durations=10 \
    ${MARK[@]+"${MARK[@]}"} "$@"
python -m repro.core.analysis.verify
python -m benchmarks.serving_benchmarks --smoke --suite all
if [ "$DIFFERENTIAL" = "1" ]; then
    python -m pytest -x -q tests/test_differential.py
fi
if [ "$SCHEDULER" = "1" ]; then
    python -m pytest -x -q tests/test_scheduler.py
    python -m benchmarks.serving_benchmarks --smoke --suite multitenant
fi
if [ "$PROPERTIES" = "1" ]; then
    python -m pytest -x -q -m "properties and not slow" \
        tests/test_properties.py tests/test_seg_kernels.py
fi
if [ "$KERNELS" = "1" ]; then
    REPRO_KERNEL_INTERPRET=1 python -m pytest -x -q \
        tests/test_seg_kernels.py tests/test_kernels.py \
        tests/test_tpu_compile.py
    python -m benchmarks.serving_benchmarks --smoke --suite kernels
fi
if [ "$OBS" = "1" ]; then
    python -m pytest -x -q tests/test_obs.py
    python -m benchmarks.serving_benchmarks --smoke --suite obs
fi
if [ "$CAPACITY" = "1" ]; then
    python -m pytest -x -q tests/test_capacity.py
    python -m benchmarks.serving_benchmarks --smoke --suite capacity
fi
if [ "$PERSIST" = "1" ]; then
    python -m pytest -x -q tests/test_persist.py
    python -m benchmarks.serving_benchmarks --smoke --suite restart
fi
