"""Persistent compiled-plan cache (core/persist.py) + warmup API,
and the cache-correctness bugfix sweep that rode along:

* restart parity: a fresh QueryService on a warm cache directory
  serves every warmed template with ZERO recompiles and bitwise the
  rows the seeding process produced — scalar and batched variants;
* degradation: corrupted files and mismatched environment
  fingerprints are invalidated (counter) and recompiled, never
  served and never fatal;
* ``warmup(templates)``: boot-time prewarming populates the
  in-memory LRU from disk (warm) or compiles+stores (cold);
* typed exceptions replace bare ``assert`` on user-facing arguments
  (``stack_params``, the QueryService constructor);
* ``explain(profile=True)`` variants live in a segregated cache and
  cannot evict hot warm-path executables;
* every LRU-bounded service map attributes its evictions to
  ``stats.evictions_by_cache`` (OBS001-enforced).
"""
import os
import shutil

import numpy as np
import pytest
from conftest import check_result

from repro.core import (ExecConfig, InvalidArgumentError, QueryService,
                        persist)
from repro.core.prepared import stack_params
from repro.core.queries import ALL

TEMPLATES = ("Q2", "Q11")      # scan filter + ordered group-by top-k
BATCHED = "Q2"
BUCKET = 4


def check(rs, oracle, name):
    assert not rs.overflow
    check_result(rs, oracle, name)


# ---------------------------------------------------------------------------
# satellite: typed exceptions instead of bare assert
# ---------------------------------------------------------------------------


def test_stack_params_typed_validation():
    with pytest.raises(InvalidArgumentError):
        stack_params([], 4)
    b = (np.float32(1.0),)
    with pytest.raises(InvalidArgumentError):
        stack_params([b, b, b], 2)          # pad_to < batch
    # InvalidArgumentError is a ValueError: existing except sites hold
    with pytest.raises(ValueError):
        stack_params([b], 0)


@pytest.mark.parametrize("kwargs", [
    {"growth": 1},                    # geometric growth impossible
    {"growth": 0},
    {"cache_capacity": 0},
    {"binding_stats_capacity": 0},
    {"max_retries": -1},
    {"persist_max_bytes": -1},
])
def test_service_ctor_typed_validation(weather_db, kwargs):
    with pytest.raises(InvalidArgumentError):
        QueryService(weather_db, **kwargs)
    with pytest.raises(ValueError):       # builtin-compatible
        QueryService(weather_db, **kwargs)


# ---------------------------------------------------------------------------
# tentpole: restart parity
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def warm_cache(weather_db, tmp_path_factory):
    """Seed a persistent cache directory once: scalar variants of
    every template plus one batched variant, returning the directory
    and the seeding process's rows for bitwise comparison."""
    d = str(tmp_path_factory.mktemp("plancache"))
    svc = QueryService(weather_db, persist_dir=d)
    rows = {n: svc.execute(ALL[n]).rows() for n in TEMPLATES}
    pq = svc.prepare(ALL[BATCHED])
    rss = svc.serve_group(pq, [pq.defaults] * 3, bucket=BUCKET)
    rows["batched"] = [rs.rows() for rs in rss]
    assert svc.stats.persist_stores == svc.stats.compiles == 3
    assert svc.persist_info().entries == 3
    return d, rows, svc.stats.snapshot()


def test_restart_zero_recompiles_bitwise_parity(weather_db, oracle,
                                                warm_cache):
    d, rows, _ = warm_cache
    svc = QueryService(weather_db, persist_dir=d)
    for name in TEMPLATES:
        rs = svc.execute(ALL[name])
        assert rs.rows() == rows[name]          # bitwise identical
        check(rs, oracle, name)
    pq = svc.prepare(ALL[BATCHED])
    rss = svc.serve_group(pq, [pq.defaults] * 3, bucket=BUCKET)
    assert [rs.rows() for rs in rss] == rows["batched"]
    # the headline: the restarted service compiled NOTHING
    assert svc.stats.compiles == 0
    assert svc.executor.compile_count == 0
    assert svc.stats.persist_hits == 3
    assert svc.stats.persist_invalidations == 0
    # warm repeats stay pure in-memory hits
    snap = svc.stats.snapshot()
    for name in TEMPLATES:
        svc.execute(ALL[name])
    d2 = svc.stats.diff(snap)
    assert d2.compiles == 0 and d2.persist_hits == 0
    assert d2.cache_hits == len(TEMPLATES)


def test_warmup_from_warm_disk_zero_compiles(weather_db, warm_cache):
    d, rows, _ = warm_cache
    svc = QueryService(weather_db, persist_dir=d)
    summary = svc.warmup([ALL[n] for n in TEMPLATES]
                         + [(ALL[BATCHED], BUCKET)])
    assert summary["compiles"] == 0
    assert summary["persist_hits"] == 3
    assert summary["variants"] == 3
    # serving after warmup: pure in-memory hits, rows unchanged
    snap = svc.stats.snapshot()
    for name in TEMPLATES:
        assert svc.execute(ALL[name]).rows() == rows[name]
    assert svc.stats.diff(snap).compiles == 0
    pq = svc.prepare(ALL[BATCHED])
    rss = svc.serve_group(pq, [pq.defaults] * 3, bucket=BUCKET)
    assert [rs.rows() for rs in rss] == rows["batched"]
    assert svc.stats.compiles == 0


def test_warmup_cold_compiles_and_stores(weather_db, tmp_path):
    d = str(tmp_path / "cold")
    svc = QueryService(weather_db, persist_dir=d)
    summary = svc.warmup([ALL["Q4"]])
    assert summary["compiles"] == 1 and summary["persist_hits"] == 0
    assert svc.stats.persist_stores == 1
    # repeated warmup is idempotent: in-memory hit, no new compile
    again = svc.warmup([ALL["Q4"]])
    assert again["compiles"] == 0 and again["cache_hits"] == 1
    # a restarted warmup is now compile-free
    svc2 = QueryService(weather_db, persist_dir=d)
    assert svc2.warmup([ALL["Q4"]])["compiles"] == 0
    assert svc2.stats.persist_hits == 1


def test_concurrent_warmup_persists_like_one_at_a_time(weather_db,
                                                       tmp_path):
    """Warm-up's pooled compiles store the same entries as compiles
    made one at a time by serving."""
    warm_dir, one_dir = str(tmp_path / "warm"), str(tmp_path / "one")
    warm = QueryService(weather_db, persist_dir=warm_dir)
    summary = warm.warmup([ALL[n] for n in TEMPLATES]
                          + [(ALL[BATCHED], BUCKET)])
    one = QueryService(weather_db, persist_dir=one_dir)
    for name in TEMPLATES:
        one.execute(ALL[name])
    pq = one.prepare(ALL[BATCHED])
    one.serve_group(pq, [pq.defaults] * 3, bucket=BUCKET)
    assert summary["compiles"] == warm.stats.persist_stores == 3
    assert one.stats.persist_stores == 3
    assert sorted(os.listdir(warm_dir)) == sorted(os.listdir(one_dir))
    assert warm.persist_info().entries == one.persist_info().entries == 3


def test_warmup_rejects_bad_batch_width(weather_db):
    svc = QueryService(weather_db)
    with pytest.raises(InvalidArgumentError):
        svc.warmup([(ALL["Q2"], 0)])


# ---------------------------------------------------------------------------
# degradation: corruption and foreign fingerprints
# ---------------------------------------------------------------------------


def _copy_cache(src: str, dst: str) -> None:
    shutil.copytree(src, dst)


def test_corrupt_entries_degrade_to_recompile(weather_db, oracle,
                                              warm_cache, tmp_path):
    d0, rows, _ = warm_cache
    d = str(tmp_path / "corrupt")
    _copy_cache(d0, d)
    files = sorted(f for f in os.listdir(d) if f.endswith(".plan"))
    assert files
    # three corruption modes across the entries: truncation, flipped
    # payload bytes, and a clobbered header
    for i, name in enumerate(files):
        p = os.path.join(d, name)
        blob = bytearray(open(p, "rb").read())
        if i % 3 == 0:
            blob = blob[:len(blob) // 2]
        elif i % 3 == 1:
            blob[len(blob) // 2] ^= 0xFF
        else:
            blob[:8] = b"XXXXXXXX"
        with open(p, "wb") as fh:
            fh.write(bytes(blob))
    svc = QueryService(weather_db, persist_dir=d)
    name = TEMPLATES[0]
    rs = svc.execute(ALL[name])
    assert rs.rows() == rows[name]
    check(rs, oracle, name)
    assert svc.stats.persist_invalidations >= 1
    assert svc.stats.persist_hits == 0
    assert svc.stats.compiles == 1          # degraded, not crashed
    # the recompile re-stored a fresh entry: a further restart hits
    assert svc.stats.persist_stores == 1
    svc2 = QueryService(weather_db, persist_dir=d)
    assert svc2.execute(ALL[name]).rows() == rows[name]
    assert svc2.stats.compiles == 0 and svc2.stats.persist_hits == 1


def test_mismatched_fingerprint_never_served(weather_db, oracle,
                                             warm_cache, tmp_path,
                                             monkeypatch):
    """A cache written by a 'different environment' (here: a patched
    jax version in the fingerprint) must be invalidated and recompiled
    — parity-tested — never loaded."""
    d0, rows, _ = warm_cache
    d = str(tmp_path / "foreign")
    _copy_cache(d0, d)
    real = persist.env_fingerprint

    def foreign():
        fp = real()
        fp["jax"] = "0.0.0-foreign"
        return fp

    monkeypatch.setattr(persist, "env_fingerprint", foreign)
    svc = QueryService(weather_db, persist_dir=d)
    name = TEMPLATES[0]
    rs = svc.execute(ALL[name])
    assert rs.rows() == rows[name]          # recompiled, still exact
    check(rs, oracle, name)
    assert svc.stats.persist_hits == 0
    assert svc.stats.persist_invalidations == 1
    assert svc.stats.compiles == 1


def test_kernel_env_is_fingerprinted(weather_db, warm_cache, tmp_path,
                                     monkeypatch):
    """REPRO_KERNEL_INTERPRET changes generated code without changing
    the plan signature or config — the fingerprint must catch it."""
    d0, rows, _ = warm_cache
    d = str(tmp_path / "kernel_env")
    _copy_cache(d0, d)
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    svc = QueryService(weather_db, persist_dir=d)
    name = TEMPLATES[0]
    assert svc.execute(ALL[name]).rows() == rows[name]
    assert svc.stats.persist_hits == 0
    assert svc.stats.persist_invalidations == 1


def test_max_bytes_prunes_oldest(weather_db, tmp_path):
    d = str(tmp_path / "bounded")
    svc = QueryService(weather_db, persist_dir=d)
    svc.execute(ALL["Q2"])
    one = svc.persist_info().bytes
    assert one > 0
    # bound the directory to ~one entry: the second store must prune
    # the first (oldest) and count the eviction
    svc2 = QueryService(weather_db, persist_dir=d,
                        persist_max_bytes=int(one * 1.5))
    svc2.execute(ALL["Q2"])                 # disk hit, no store
    svc2.execute(ALL["Q4"])                 # store -> prune Q2's entry
    assert svc2.stats.persist_stores == 1
    assert svc2.stats.evictions_by_cache.get("persist", 0) >= 1
    assert svc2.persist_info().bytes <= int(one * 1.5)


def test_disk_roundtrip_unit(tmp_path):
    """PlanDiskCache unit semantics without a service: miss -> store
    -> hit; wrong fingerprint -> invalid AND deleted (second lookup
    is a clean miss)."""
    c = persist.PlanDiskCache(str(tmp_path / "unit"))
    fp = {"v": 1}
    assert c.lookup("k" * 64, fp) == ("miss", None)
    entry = {"schema": {0: ("num", None)}, "payload": b"\x01\x02",
             "in_tree": b"it", "out_tree": b"ot"}
    assert c.store("k" * 64, fp, entry) == 0
    status, got = c.lookup("k" * 64, fp)
    assert status == "hit" and got["payload"] == b"\x01\x02"
    assert c.lookup("k" * 64, {"v": 2})[0] == "invalid"
    assert c.lookup("k" * 64, fp) == ("miss", None)   # deleted
    assert c.info().entries == 0


# ---------------------------------------------------------------------------
# satellite: profile-cache segregation
# ---------------------------------------------------------------------------


def test_explain_profile_cannot_evict_warm_plans(weather_db, oracle):
    """The regression: with a capacity-1 level-1 cache, repeated
    explain(profile=True) used to evict the hot serving executable.
    Profile variants now live in their own cache — N explain calls
    leave warm-path hits and the serving cache untouched."""
    svc = QueryService(weather_db, cache_capacity=1)
    check(svc.execute(ALL["Q4"]), oracle, "Q4")
    size = svc.cache_size()
    snap = svc.stats.snapshot()
    for _ in range(3):
        svc.explain(ALL["Q4"], profile=True)
    delta = svc.stats.diff(snap)
    assert svc.cache_size() == size             # serving cache intact
    assert delta.cache_hits == 0                # no serving traffic
    assert delta.cache_misses == 0
    assert delta.compiles == 1                  # one profile variant
    assert svc.stats.compiles == svc.executor.compile_count
    # the warm path is still a pure hit — the executable survived
    snap = svc.stats.snapshot()
    check(svc.execute(ALL["Q4"]), oracle, "Q4")
    d2 = svc.stats.diff(snap)
    assert d2.compiles == 0 and d2.cache_hits == 1
    assert svc.stats.evictions == 0


def test_profile_cache_is_bounded(weather_db):
    svc = QueryService(weather_db, cache_capacity=1)
    svc.explain(ALL["Q4"], profile=True)
    svc.explain(ALL["Q3"], profile=True)
    assert len(svc._profile_cache) == 1
    assert svc.stats.evictions_by_cache.get("profile_plans", 0) == 1
    assert svc.stats.evictions == 0             # level-1 untouched


# ---------------------------------------------------------------------------
# satellite: per-cache eviction counters
# ---------------------------------------------------------------------------


def test_binding_stats_evictions_counted(weather_db):
    svc = QueryService(weather_db, binding_stats_capacity=1)
    pq = svc.prepare(ALL["Q2"])
    svc.execute(pq)                              # binding 1
    svc.execute(pq, bindings=("PRCP", 100.0))    # binding 2 evicts 1
    assert svc.stats.evictions_by_cache.get("bindings", 0) >= 1
    assert len(svc._bindings) == 1


def test_good_cfg_and_history_evictions_counted(weather_db):
    svc = QueryService(weather_db)
    svc._good_cfg_capacity = 1      # shrink the shared per-sig bound
    svc.execute(ALL["Q4"])
    svc.execute(ALL["Q3"])
    ev = svc.stats.evictions_by_cache
    assert ev.get("good_cfg", 0) >= 1
    assert ev.get("sig_history", 0) >= 1
    assert len(svc._good_cfg) == 1


def test_row_cost_evictions_counted(weather_db):
    svc = QueryService(weather_db)
    svc._good_cfg_capacity = 1
    svc.row_cost(svc.prepare(ALL["Q2"]))
    svc.row_cost(svc.prepare(ALL["Q4"]))
    assert svc.stats.evictions_by_cache.get("row_cost", 0) >= 1


def test_level1_evictions_keep_legacy_counter(weather_db, oracle):
    """Level-1 evictions count BOTH in the legacy ``evictions`` total
    and under the per-cache label — dashboards keep working."""
    svc = QueryService(weather_db, cache_capacity=1)
    check(svc.execute(ALL["Q4"]), oracle, "Q4")
    check(svc.execute(ALL["Q2"]), oracle, "Q2")
    assert svc.stats.evictions == 1
    assert svc.stats.evictions_by_cache.get("plans", 0) == 1
