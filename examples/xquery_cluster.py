"""Cluster-mode XQuery: shard_map over a data axis of every device.

    PYTHONPATH=src python examples/xquery_cluster.py

On the CPU, simulate eight devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; on a TPU host
the mesh spans its chips. The database gets one partition per device.

The same compiled plan as quickstart, but executed as a true SPMD
program over the mesh with lax collectives at the exchange points:
all_gather for the hybrid-hash join build side and psum for the
two-step aggregation — the Hyracks connector analogues
(DESIGN.md §2). Also runs the grace-repartition strategy for the
large-large join (Q8), mirroring the paper's hybrid-vs-grace
discussion.
"""
import time

import jax

from repro import compile_cache
from repro.core import ExecConfig, Executor, QueryService, compile_query
from repro.core.queries import ALL
from repro.data.weather import WeatherSpec, build_database


def main() -> None:
    compile_cache.enable()
    n = len(jax.devices())
    print(f"devices: {n}")
    db = build_database(WeatherSpec(num_stations=16,
                                    years=(1976, 2000, 2001),
                                    days_per_year=4),
                        num_partitions=n)
    mesh = jax.make_mesh((n,), ("data",), (jax.sharding.AxisType.Auto,))

    for name, strat in [("Q5", "broadcast"), ("Q7", "broadcast"),
                        ("Q8", "repartition")]:
        ex = Executor(db, ExecConfig(join_strategy=strat))
        plan = compile_query(ALL[name])
        t0 = time.time()
        rs = ex.run(plan, mode="spmd", mesh=mesh)
        dt = time.time() - t0
        if name in ("Q7", "Q8"):
            print(f"{name} [{strat:11s}] -> {rs.scalar():9.3f} "
                  f"({dt:.2f}s incl. compile)")
        else:
            print(f"{name} [{strat:11s}] -> {len(rs.rows())} rows "
                  f"({dt:.2f}s incl. compile)")

    # Service mode: the same SPMD path behind the adaptive layer —
    # statistics-presized caps, and the second execution of each query
    # skips trace+compile via the plan cache.
    svc = QueryService(db, mode="spmd", mesh=mesh)
    for name in ("Q5", "Q8"):
        t0 = time.time()
        svc.execute(ALL[name])
        cold = time.time() - t0
        t0 = time.time()
        svc.execute(ALL[name])
        warm = time.time() - t0
        print(f"{name} [service    ] cold {cold:.2f}s -> warm "
              f"{warm*1e3:.1f}ms ({cold / max(warm, 1e-9):.0f}x "
              f"amortization)")
    print(f"service stats: {svc.stats}")


if __name__ == "__main__":
    main()
