"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's archive from the seed, ingests it through the
program's bulk shredder, loads it on the device (``QueryService``, one
chip under vmap or a mesh of chips in spmd mode) and warms every plan
the mix will use. Then one closed-loop client sends the mix's requests
for ``--seconds``: ``QueryService.execute(text)`` and then
``ResultSet.rows()``, the next request once the rows are decoded.
After the window every answer is compared with the plain numpy
reference. The last line of standard output is the result, as JSON;
the numbers compared, each beside its limit, are the last lines of
standard error. With ``--trace 1`` the window runs under the profiler
and the result carries the per-layer metrics.

It exits nonzero and prints no result where JAX finds no TPU, fewer
chips than the cell asks for, or a kernel override in the environment.
JAX's compilation cache is kept in ``.jax_cache/`` at the root of the
checkout, so only a checkout's first run compiles.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import data, reference, spec, stats, traffic  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench import work  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
KERNEL_OVERRIDES = ("REPRO_FORCE_JNP", "REPRO_KERNEL_INTERPRET")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Refused(Exception):
    """The machine cannot run this cell as stated."""


@dataclasses.dataclass
class Window:
    requests: list = dataclasses.field(default_factory=list)
    rows: list = dataclasses.field(default_factory=list)     # None: failed
    errors: list = dataclasses.field(default_factory=list)
    latency_s: list = dataclasses.field(default_factory=list)
    decode_s: list = dataclasses.field(default_factory=list)
    start: float = 0.0
    seconds: float = 0.0

    @property
    def attempted(self) -> int:
        return len(self.requests)

    @property
    def failed(self) -> int:
        return sum(r is None for r in self.rows)


@dataclasses.dataclass
class Context:
    """What a metric reader reads (metrics/<name>.py)."""
    cell: dict
    setup: dict            # setup_s and its parts, seconds
    window: Window
    trace: dict | None     # trace.summarize's reduction, traced runs only
    work_bytes: int        # logical kernel bytes of the window's requests
    peak: dict             # peaks.json entry of this device kind


def serve(svc, stream, seconds: float, traced: bool) -> Window:
    """The closed loop: send, decode, send the next, until ``seconds``
    have passed; the window closes when the last request is decoded."""
    import jax
    span = jax.profiler.TraceAnnotation if traced else (
        lambda name: contextlib.nullcontext())
    w = Window()
    clock = time.perf_counter
    w.start = deadline = clock()
    deadline += seconds
    done = w.start
    while clock() < deadline:
        with span("bench.gen"):
            req = next(stream)
        sent = clock()
        rows, err, decoded = None, None, sent
        try:
            with span(f"bench.execute:{req.name}"):
                rs = svc.execute(req.text)
            executed = clock()
            with span(f"bench.decode:{req.name}"):
                rows = rs.rows()
            decoded = clock()
        except Exception as e:          # a failed request is counted
            err = f"{type(e).__name__}: {e}"
            executed = decoded = clock()
        done = decoded
        w.requests.append(req)
        w.rows.append(rows)
        w.errors.append(err)
        w.latency_s.append(decoded - sent)
        w.decode_s.append(decoded - executed)
    w.seconds = done - w.start
    return w


def check(cfg: dict, rec: dict, w: Window) -> tuple[int, float, int]:
    """(wrong answers, widest relative gap, answers compared) of every
    answer in the window against the reference."""
    ref = reference.Reference(cfg, rec)
    wrong, rel, compared = 0, 0.0, 0
    for req, rows in zip(w.requests, w.rows):
        if rows is None:
            continue
        try:
            got = reference.normalize(req.name, rows)
        except ValueError:
            wrong += 1
            continue
        exact, r = reference.compare(req.name, got,
                                     ref.answer(req.name, req.args))
        wrong += not exact
        rel = max(rel, r)
        compared += 1
    return wrong, rel, compared


def make_service(db, cfg: dict, devices):
    import jax
    from repro.core import QueryService
    if cfg["mode"] == "spmd":
        n = cfg["num_partitions"]
        mesh = jax.make_mesh((n,), ("data",), (jax.sharding.AxisType.Auto,),
                             devices=devices[:n])
        return QueryService(db, mode="spmd", mesh=mesh)
    return QueryService(db)


def run_cell(bench: dict, cell: dict, cfg: dict, mix: dict, limits: dict,
             seed: int, seconds: float, traced: bool, devices) -> dict:
    """Set up, serve the window, check it; the result line as a dict.
    ``devices`` are the chips the cell runs on."""
    import jax
    kind = devices[0].device_kind
    peaks = spec.peaks()
    if devices[0].platform == "tpu" and kind not in peaks:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    setup = {}

    t = time.perf_counter()
    rec = data.records(cfg, seed)
    db = data.build(cfg, rec)
    setup["ingest_s"] = time.perf_counter() - t

    t = time.perf_counter()
    svc = make_service(db, cfg, devices)
    jax.block_until_ready(svc.executor.tables)
    setup["load_s"] = time.perf_counter() - t

    t = time.perf_counter()
    gen = traffic.Generator(mix, cfg, seed)
    warm = gen.warm_set(reference.Sizes(cfg, rec).size)
    svc.warmup([r.text for r in warm])
    for r in warm:
        svc.execute(r.text).rows()
    setup["warmup_s"] = time.perf_counter() - t
    log(f"set-up: {len(rec['value'])} readings in {cfg['num_partitions']} "
        f"partitions, ingest {setup['ingest_s']:.3f} s, device load "
        f"{setup['load_s']:.3f} s, warm-up {setup['warmup_s']:.3f} s "
        f"({len(warm)} plans; {svc.stats.compiles} compiled)")

    gc.collect()
    gc.freeze()
    before = svc.stats.snapshot()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    try:
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # spans only, no Python calls
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        w = serve(svc, iter(gen), seconds, traced)
        setup["setup_s"] = w.start - T0
        if traced:
            jax.profiler.stop_trace()
            tr = T.read(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    during = svc.stats.diff(before)
    log(f"window: {w.attempted} requests in {w.seconds:.3f} s, "
        f"{w.failed} failed; compiles in window {during.compiles}, "
        f"regrowth retries {during.retries}")
    by_template: dict = {}
    for req, lat in zip(w.requests, w.latency_s):
        by_template.setdefault(req.name, []).append(lat)
    log("latency by template (n, median s, max s): " + "; ".join(
        f"{k} {len(v)} {stats.percentile(v, 50):.4f} {max(v):.4f}"
        for k, v in sorted(by_template.items())))
    for err in sorted({e for e in w.errors if e})[:5]:
        log(f"failed request: {err[:300]}")
    peak_bytes = max(d.memory_stats()["peak_bytes_in_use"] for d in devices) \
        if devices[0].platform == "tpu" else 0
    del svc, db
    gc.unfreeze()

    summary = None
    if traced:
        summary = T.summarize(tr)
        log("trace layout: " + "; ".join(tr.layout[:40]))
    sizes = reference.Sizes(cfg, rec)
    ctx = Context(cell, setup, w, summary,
                  sum(work.request_bytes(sizes, r.name, r.args)
                      for r in w.requests), peaks.get(kind, {}))
    kind_key = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in spec.metrics(bench, kind_key, cell["name"]):
        value = spec.metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    wrong, rel, compared = check(cfg, rec, w)
    checks = {
        "failed": {"value": w.failed, "limit": 0},
        "unchecked": {"value": w.attempted - w.failed - compared, "limit": 0},
        "wrong_answers": {"value": wrong, "limit": limits["wrong_answers"]},
        "max_rel_err": {"value": rel, "limit": limits["max_rel_err"]},
    }
    correct = w.attempted > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": correct, "attempted": w.attempted, "failed": w.failed,
           "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    for var in KERNEL_OVERRIDES:
        if os.environ.get(var):
            raise Refused(f"{var} is set; the benchmark runs the default "
                          f"kernel routing")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell["chips"]:
        raise Refused(f"{cell['name']} needs {cell['chips']} chips, "
                      f"JAX found {len(devices)}")
    log(f"imports and {len(devices)} x {devices[0].device_kind} found: "
        f"{time.perf_counter() - T0:.3f} s")
    out = run_cell(bench, cell, spec.config(bench, cell["config"]),
                   spec.traffic(cell["traffic"]), spec.limits(cell["name"]),
                   args.seed, args.seconds, bool(args.trace),
                   devices[:cell["chips"]])
    print(json.dumps(out), flush=True)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        log(f"chipbench: refused: {e}")
        sys.exit(2)
