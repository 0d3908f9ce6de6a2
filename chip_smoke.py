"""Bring-up check: the XQuery serving path on TPU, at sensor-archive scale.

    python chip_smoke.py              # one chip: Q1-Q12 and variants
    python chip_smoke.py --chips 4    # 2x2 host: Q5-Q12 in spmd mode

Builds the GHCN-Daily-style archive (500 stations x 50 years x 8 days
x 5 reading types = 1,000,000 readings, about 7M XML nodes in four
partitions), loads it on the device, serves the queries through
``QueryService`` — warmup, then ``execute``, then constant variants
through ``submit``/``drain`` — and checks every answer against the
plain numpy reference (``repro.data.reference``). One chip runs the
four partitions under vmap; ``--chips 4`` puts one partition on each
chip (shard_map over the ``data`` axis) and serves the joins and
grouped queries, whose exchanges cross chips.

It refuses to run off a TPU or with a kernel override set, and exits
nonzero, printing no result line, on any mismatch, ticket error or
kernel that is not compiled Pallas. The last line of a passing run is
``{"ok": true, "device": {...}}``. Times printed here are bring-up
readings of one run, not benchmark results.

JAX's compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when
set, else to ``.jax_cache/`` beside this file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
NUM_PARTITIONS = 4
NUM_STATIONS = 500
YEARS = tuple(range(1960, 2010))
DAYS_PER_YEAR = 8
MESH_QUERIES = ("Q5", "Q6", "Q7", "Q8", "Q9", "Q10", "Q11", "Q12")
# served plans whose HLO must hold the Pallas kernels
KERNEL_QUERIES = ("Q5", "Q8", "Q9", "Q11")
KERNEL_OVERRIDES = ("REPRO_FORCE_JNP", "REPRO_KERNEL_INTERPRET")


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


def reckon_bytes(db) -> int:
    """Bytes of the node tables the device holds: per padded node six
    4-byte columns plus ``field_map``'s F int32 slots."""
    total = 0
    for coll in db.collections.values():
        n = len(coll.partitions) * -(-max(
            t.num_nodes for t in coll.partitions) // 128) * 128
        total += n * 4 * (6 + coll.partitions[0].field_map.shape[1])
    return total


def memory_line(devices) -> str:
    return ", ".join(f"{d.id}: {gib(d.memory_stats()['bytes_in_use'])}"
                     for d in devices)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def check_policy(name: str, configs) -> None:
    """Every plan the service compiled for ``name`` routes its join
    probe and its segment engine to the Pallas kernels."""
    if not configs:
        fail(f"{name}: no compiled plan in the service cache")
    for cfg in configs:
        if not (cfg.use_pallas_join and cfg.use_pallas_segments):
            fail(f"{name}: kernel policy fell back: "
                 f"use_pallas_join={cfg.use_pallas_join}, "
                 f"use_pallas_segments={cfg.use_pallas_segments}")


def serve(svc, ref, names) -> dict:
    """Warm, execute twice and check each query; report its times.
    Returns the compiled plan each query's last ``execute`` ran."""
    from repro.data import reference as R
    used = {}
    log("query  kind     warmup_s  first_s  warm_s    rows  "
        "pallas join/segments")
    for name in names:
        text = R.query_text(name)
        n0 = len(svc.cached_plans())
        _, warm_up = timed(lambda: svc.warmup([text]))
        rows, first = timed(lambda: svc.execute(text).rows())
        rows, warm = timed(lambda: svc.execute(text).rows())
        plans = svc.cached_plans()
        check_policy(name, [cp.config for cp in plans[n0:]])
        used[name] = plans[-1]        # the entry the last execute hit
        err = R.mismatch(name, R.normalize(name, rows), ref.answer(name))
        if err:
            fail(err)
        cfg = used[name].config
        log(f"{name:<6} {R.KIND[name]:<8} {warm_up:8.3f} {first:8.3f} "
            f"{warm:7.3f} {len(rows):7d}  {cfg.use_pallas_join}/"
            f"{cfg.use_pallas_segments}")
    return used


def show_compiled(used: dict) -> None:
    """Read the executables that served the checked answers: the
    kernel plans' HLO must hold the Pallas kernels
    (``tpu_custom_call``); print what each plan needs on the device."""
    largest = None
    for name, cp in used.items():
        m = cp.fn.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes)
        kernels = ""
        if name in KERNEL_QUERIES:
            if "tpu_custom_call" not in cp.fn.as_text():
                fail(f"{name}: compiled HLO holds no tpu_custom_call")
            kernels = "tpu_custom_call in compiled HLO; "
        log(f"{name}: {kernels}memory_analysis: arguments "
            f"{gib(m.argument_size_in_bytes)}, outputs "
            f"{gib(m.output_size_in_bytes)}, temps "
            f"{gib(m.temp_size_in_bytes)}")
        if largest is None or total > largest[1]:
            largest = (name, total, m)
    log(f"largest served plan: {largest[0]} ({gib(largest[1])}): "
        f"{largest[2]}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: serve Q5-Q12 over a 4-chip mesh only")
    args = ap.parse_args()
    for var in KERNEL_OVERRIDES:
        if os.environ.get(var):
            fail(f"{var} is set; the chip run takes the default "
                 f"kernel routing")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache
    cache_dir = compile_cache.enable()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, "
             f"found {len(devices)}")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache {cache_dir}")

    from repro.core import QueryService
    from repro.data import reference as R
    from repro.data.weather import WeatherSpec, build_database

    spec = WeatherSpec(num_stations=NUM_STATIONS, years=YEARS,
                       days_per_year=DAYS_PER_YEAR)
    db, ingest_s = timed(lambda: build_database(
        spec, num_partitions=NUM_PARTITIONS))
    sensor_nodes = sum(t.num_nodes for t in
                       db.collection("/sensors").partitions)
    nodes = sum(t.num_nodes for c in db.collections.values()
                for t in c.partitions)
    ref, ref_s = timed(lambda: R.Reference(spec))
    log(f"ingest: {len(ref.value)} readings, {nodes} XDM nodes "
        f"({sensor_nodes} in /sensors) in {NUM_PARTITIONS} partitions: "
        f"{ingest_s:.3f} s; numpy reference {ref_s:.3f} s")
    log(f"reckoned node tables: {gib(reckon_bytes(db))}")

    if args.chips == 4:
        mesh = jax.make_mesh((NUM_PARTITIONS,), ("data",),
                             (jax.sharding.AxisType.Auto,),
                             devices=devices[:NUM_PARTITIONS])
        kwargs, names = {"mode": "spmd", "mesh": mesh}, MESH_QUERIES
    else:
        kwargs, names = {}, tuple(R.KIND)

    def load():
        svc = QueryService(db, **kwargs)
        jax.block_until_ready(svc.executor.tables)
        return svc

    svc, load_s = timed(load)
    log(f"device load ({svc.mode} mode): {load_s:.3f} s; bytes_in_use "
        f"{memory_line(devices[:args.chips])}")

    show_compiled(serve(svc, ref, names))

    if args.chips == 1:
        tickets = [(n, a, svc.submit(R.query_text(n, a), tenant=n))
                   for n, a in R.VARIANTS]
        _, drain_s = timed(svc.drain)
        for name, a, t in tickets:
            if t.error is not None:
                fail(f"{name}{a}: ticket error {t.error!r}")
            err = R.mismatch(name, R.normalize(name, t.result.rows()),
                             ref.answer(name, a))
            if err:
                fail(f"variant {a}: {err}")
        log(f"submit/drain: {len(tickets)} variants, "
            f"{svc.stats.batches} batched dispatches, {drain_s:.3f} s")

    log(f"bytes_in_use after serving: "
        f"{memory_line(devices[:args.chips])}; service {svc.stats}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
