"""The async serving runtime's unit and property tests: virtual-clock
SLO-deadline admission, deficit-round-robin tenant fairness under an
adversarial arrival mix, cost-based bucket fitting (DP optimality on
hand cases + never-worse-than-pow2), and end-to-end submit/drain
parity on the weather database — including batched dispatch under
shard_map on a 1-device mesh."""
import jax
import pytest

from repro.core import ExecConfig, QueryService
from repro.core.serving import (AdmissionQueue, CostBasedBucketing,
                                FairScheduler, Pow2Bucketing, Ticket,
                                VirtualClock, next_pow2)
from repro.core.serving.bucketing import fit_buckets
from repro.core.workload import (DEFAULT_TENANTS, make_tenant_traffic,
                                 variant_grid)

STATIONS = ["GHCND:USW00012836", "GHCND:USW00014771",
            "GHCND:USW90000002", "GHCND:USW90000003",
            "GHCND:USW90000004"]
YEARS = (1976, 1999, 2000, 2001, 2003, 2004)


def tk(seq, tenant="t", arrival=0.0, slo=10.0):
    return Ticket(seq=seq, tenant=tenant, query=None, values=(),
                  arrival=arrival, deadline=arrival + slo)


# -- admission queue ---------------------------------------------------------


def test_window_closes_at_slo_deadline():
    clock = VirtualClock()
    q = AdmissionQueue(clock, window=2.0, max_fill=100)
    q.submit(tk(0, arrival=0.0))
    clock.advance(1.0)
    q.submit(tk(1, arrival=1.0))
    # the window's deadline is fixed by its FIRST ticket: 0.0 + 2.0
    assert q.pop_due() == []
    assert q.next_close() == 2.0
    clock.advance_to(2.0)
    got = q.pop_due()
    assert [t.seq for t in got] == [0, 1]
    assert q.closed_by_deadline == 1 and q.closed_by_fill == 0


def test_window_closes_on_fill_and_opens_next():
    clock = VirtualClock()
    q = AdmissionQueue(clock, window=100.0, max_fill=3)
    for i in range(5):
        q.submit(tk(i))
    # first window filled (3) and is due immediately; the remaining 2
    # wait for their own deadline
    got = q.pop_due()
    assert [t.seq for t in got] == [0, 1, 2]
    assert q.closed_by_fill == 1
    assert len(q) == 2
    assert q.flush() and len(q) == 0


def test_virtual_clock_is_monotonic():
    clock = VirtualClock(5.0)
    clock.advance_to(3.0)       # past timestamps never rewind
    assert clock.now() == 5.0
    with pytest.raises(AssertionError):
        clock.advance(-1.0)


# -- deficit round-robin fairness --------------------------------------------


def test_drr_budgeted_sweeps_rotate_start_tenant():
    """A per-sweep budget smaller than one tenant's quantum must not
    permanently starve later-offered tenants: sweep starts rotate, so
    every backlogged tenant is served within a bounded number of
    budgeted sweeps (the no-starvation guarantee extended to
    budget < sum of active quanta)."""
    sched = FairScheduler(quantum=4)
    sched.offer([tk(i, "A") for i in range(40)])
    sched.offer([tk(100 + i, "B") for i in range(40)])
    for _ in range(10):
        got = sched.select(budget=4)
        assert len(got) == 4
    assert sched.served.get("A", 0) > 0
    assert sched.served.get("B", 0) > 0
    assert abs(sched.served["A"] - sched.served["B"]) <= 4


def test_drr_zero_budget_rejected():
    sched = FairScheduler()
    sched.offer([tk(0, "A")])
    with pytest.raises(AssertionError):
        sched.select(budget=0)


def test_padded_rows_metric_reads_dispatch_log():
    """The padding metric unpacks the runtime's actual 4-tuple
    dispatch-log records (sig, size, bucket, row_cost)."""
    from repro.core.serving.bucketing import padded_rows
    log = [("sigA", 3, 4, 10), ("sigB", 2, 2, 7), ("sigA", 1, 4, 10)]
    assert padded_rows(log) == (4 - 3) * 10 + 0 + (4 - 1) * 10


def test_drr_no_tenant_starved_under_adversarial_mix():
    """Flooding tenant A (90 requests, all queued first) must not
    starve B (10 requests): while both have backlog, per-sweep service
    differs by at most the quantum, and B drains within ceil(10/q)
    sweeps — not after A."""
    q = 4
    sched = FairScheduler(quantum=q)
    sched.offer([tk(i, "A") for i in range(90)])
    sched.offer([tk(100 + i, "B") for i in range(10)])
    sweeps = 0
    while sched.backlog():
        before = dict(sched.served)
        picked = sched.select()
        assert picked, "backlog must always make progress"
        sweeps += 1
        a = sched.served.get("A", 0) - before.get("A", 0)
        b = sched.served.get("B", 0) - before.get("B", 0)
        if sweeps <= 2:     # both tenants still backlogged
            assert abs(a - b) <= q, (sweeps, a, b)
        if sweeps == 3:     # ceil(10/4): B fully served by now
            assert sched.served["B"] == 10
    assert sched.served == {"A": 90, "B": 10}
    assert sweeps >= 90 // q


def test_drr_idle_tenant_does_not_hoard_credit():
    sched = FairScheduler(quantum=2)
    sched.offer([tk(0, "A")])
    sched.select()
    # A drained with leftover credit; a later flood must not burst
    # past the quantum on accumulated deficit
    sched.offer([tk(i, "A") for i in range(1, 10)])
    picked = sched.select()
    assert len(picked) == 2


# -- cost-based bucketing ----------------------------------------------------


def test_fit_buckets_beats_pow2_on_odd_sizes():
    hist = {5: 1, 6: 1, 7: 1}
    assert fit_buckets(hist, max_buckets=1, row_cost=1,
                       compile_cost=0.0) == (7,)
    # pow2 pads all three to 8: waste 3+2+1=6; one fitted bucket of 7
    # wastes 2+1+0=3
    pow2_waste = sum(next_pow2(s) - s for s in hist)
    fit_waste = sum(7 - s for s in hist)
    assert fit_waste < pow2_waste


def test_fit_buckets_dp_splits_when_worth_it():
    hist = {2: 10, 16: 1}
    # cheap compiles: keep both sizes exact
    assert fit_buckets(hist, max_buckets=2, row_cost=1,
                       compile_cost=1.0) == (2, 16)
    # a compile costing more than every padded row collapses to one
    assert fit_buckets(hist, max_buckets=2, row_cost=1,
                       compile_cost=1000.0) == (16,)


def test_fit_buckets_never_worse_than_pow2_at_equal_budget():
    """The structural guarantee the benchmark gate leans on: with the
    bucket budget pow2 spent on the same size mix, the DP's padding is
    <= pow2's."""
    import itertools
    for sizes in itertools.combinations((1, 2, 3, 5, 6, 7, 9, 12, 15),
                                        3):
        hist = {s: 1 + (s % 3) for s in sizes}
        k = len({next_pow2(s) for s in hist})
        fitted = fit_buckets(hist, max_buckets=k, row_cost=1,
                             compile_cost=0.0)
        assert len(fitted) <= k

        def waste(ladder):
            return sum(c * (min(b for b in ladder if b >= s) - s)
                       for s, c in hist.items())

        assert waste(fitted) <= waste(sorted(
            {next_pow2(s) for s in hist})), (sizes, fitted)


def test_cost_policy_cold_start_falls_back_to_pow2():
    pol = CostBasedBucketing()
    assert pol.bucket_for("sig", 5) == 8
    assert pol.fallbacks == 1
    pol.observe("sig", 5)
    assert pol.bucket_for("sig", 5) == 5     # fitted on next window
    assert pol.bucket_for("sig", 3) == 5     # covered by the ladder
    assert pol.bucket_for("sig", 9) == 16    # beyond history: pow2


def test_cost_policy_frozen_serves_preseeded_ladder():
    pol = CostBasedBucketing(frozen=True)
    pol.preseed("sig", [4, 6, 6])
    assert pol.bucket_for("sig", 5) == 6
    pol.observe("sig", 12)                   # frozen: no refit
    assert pol.bucket_for("sig", 5) == 6


# -- end-to-end: submit/drain over the weather db ----------------------------


@pytest.fixture(scope="module")
def sched_services(weather_db):
    return {
        "direct": QueryService(weather_db),
        "sched": QueryService(weather_db),
    }


def test_submit_drain_parity_and_fair_interleave(weather_db,
                                                 sched_services):
    """Two tenants submit interleaved constant-variants; scheduled
    results are bit-identical to direct execution and every request
    completes within its admission window's virtual deadline."""
    texts = variant_grid("Q1", STATIONS, YEARS, 6) \
        + variant_grid("Q2", STATIONS, YEARS, 4)
    direct = [sched_services["direct"].execute(t) for t in texts]
    svc = sched_services["sched"]
    rt = svc.runtime(window=1.0, max_fill=8, quantum=4)
    tickets = [rt.submit(t, tenant="A" if i % 2 else "B")
               for i, t in enumerate(texts)]
    done = rt.drain()
    assert done == tickets
    for d, t in zip(direct, tickets):
        assert t.error is None
        assert d.rows() == t.result.rows()
        # deterministic virtual latency: a fill-closed window
        # dispatches immediately (latency 0), a deadline-closed one at
        # exactly the admission window (no service-time measurement in
        # tests)
        assert t.latency in (0.0, 1.0)
    assert any(t.latency == 1.0 for t in tickets)
    assert rt.stats.batches >= 2          # grouped, not per-request
    assert svc.stats.batched_requests >= 8


def test_sparse_arrival_closes_window_at_deadline_not_next_event(
        weather_db):
    """An arrival that crosses a pending window's deadline must first
    close that window AT the deadline: the early request's latency is
    the admission window, never the gap to the next arrival — and the
    two requests never share a dispatch (the first one's SLO budget
    was spent before the second arrived)."""
    svc = QueryService(weather_db)
    rt = svc.runtime(window=2.0, max_fill=16)
    q = variant_grid("Q2", STATIONS, YEARS, 2)
    t_a = rt.submit(q[0], tenant="A", at=0.0)
    t_b = rt.submit(q[1], tenant="A", at=5.0)
    rt.drain()
    assert t_a.completion == 2.0 and t_a.latency == 2.0
    assert t_b.completion == 7.0 and t_b.latency == 2.0
    # default SLO is 2x the window; both met it exactly
    assert rt.stats.slo_misses == 0
    # a tighter SLO than the admission window is necessarily missed,
    # and counted
    t_c = rt.submit(q[0], tenant="A", at=10.0, slo=0.5)
    rt.drain()
    assert t_c.latency == 2.0
    assert rt.stats.slo_misses == 1


def test_runtime_rejects_unknown_policy_name(weather_db):
    svc = QueryService(weather_db)
    with pytest.raises(KeyError):
        svc.runtime(policy="powto")


def test_runtime_open_loop_traffic_all_served(weather_db):
    traffic = make_tenant_traffic(DEFAULT_TENANTS, STATIONS[:5], YEARS,
                                  total=12, seed=3)
    svc = QueryService(weather_db)
    for at, tenant, _, text in traffic:
        svc.submit(text, tenant=tenant, at=at)
    tickets = svc.drain()
    assert len(tickets) == 12
    assert all(t.error is None and t.result is not None
               for t in tickets)
    # arrival order is preserved per ticket, and latencies are bounded
    # by window + dispatch (deterministic clock: exactly the window
    # for deadline-closed windows)
    assert all(t.latency <= 2.0 * svc._runtime.queue.window + 1e-9
               for t in tickets)


def test_scheduled_batch_under_shard_map_1dev():
    """Batched dispatch composes with shard_map: a 1-device mesh
    (num_partitions must equal mesh size) serves a batch through the
    spmd path with results identical to per-request spmd execution.
    (The 8-device version runs in tests/test_distributed.py.)"""
    from repro.data.weather import WeatherSpec, build_database
    db = build_database(WeatherSpec(num_stations=5,
                                    years=(1976, 2000),
                                    days_per_year=2),
                        num_partitions=1)
    mesh = jax.make_mesh((1,), ("data",), (jax.sharding.AxisType.Auto,))
    texts = variant_grid("Q1", STATIONS, YEARS, 3) \
        + variant_grid("Q3", STATIONS, YEARS, 3)
    svc = QueryService(db, mode="spmd", mesh=mesh)
    per_req = [svc.execute(t) for t in texts]
    svc_b = QueryService(db, mode="spmd", mesh=mesh)
    batched = svc_b.execute_batch(texts)
    for a, b in zip(per_req, batched):
        assert a.rows() == b.rows()
    assert svc_b.stats.batches == 2


def test_binding_stats_map_is_bounded(weather_db):
    """The exact-bindings stats map must not grow past its capacity
    under adversarially distinct bindings (long-running services would
    otherwise leak host memory)."""
    svc = QueryService(weather_db, binding_stats_capacity=4)
    for k in range(9):
        svc.execute(variant_grid("Q2", STATIONS, YEARS, 9)[k])
    stats = svc.binding_stats()
    assert len(stats) <= 4
    assert svc.stats.exact_misses == 9
    # most-recent bindings survive (LRU eviction order)
    assert all(count == 1 for count in stats.values())
