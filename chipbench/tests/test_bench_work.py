"""``work.request_bytes``: hand-counted cases, and a proof that the
logical count never exceeds the bytes the program's kernel operators
really take in and give out."""
import itertools

import numpy as np
import pytest

from chipbench import reference, spec, traffic, work


@pytest.fixture(scope="module")
def ref(cfg, rec):
    return reference.Reference(cfg, rec)


def test_hand_counted(ref):
    # 9 stations x 9 dates (3 years x 3 days) x 5 types = 405 readings
    assert len(ref.value) == 405
    # group: 81 TMAX rows x (key, value) + 9 groups x (count, avg)
    assert work.request_bytes(ref, "Q9", {"datatype": "TMAX"}) == 4 * (162 + 18)
    # top-k adds 9 groups x (sum, key) + k indices
    assert work.request_bytes(ref, "Q11", {"datatype": "TMAX", "k": 3}) == \
        4 * (162 + 18) + 4 * (18 + 3)
    # join: 9 stations, 27 TMAX readings of 2000, all 27 matched
    assert work.request_bytes(ref, "Q6", {"datatype": "TMAX", "year": 2000}) == \
        4 * (9 + 27 + 27)
    # self-join on (station, date): 81 TMIN, 81 TMAX, 81 pairs
    assert work.request_bytes(ref, "Q8", {}) == 4 * (2 * 162 + 81)
    assert work.request_bytes(ref, "Q2", {"datatype": "TMAX",
                                          "threshold": 0.5}) == 0


def test_never_above_the_kernels_real_inputs(cfg, rec, db, monkeypatch):
    """Each kernel entry point records the bytes of its arguments and
    results as the plan is traced (per partition under the vmap); the
    logical count of every analytic template stays at or below them."""
    from repro.core import ExecConfig, QueryService
    from repro.kernels import ops

    seen = []

    def nbytes(*xs):
        return sum(4 * int(np.prod(x.shape)) for x in xs)

    def hook(name, fn):
        def wrapped(*a, **k):
            out = fn(*a, **k)
            flat = [x for v in list(a) + list(k.values()) + [out]
                    for x in (v if isinstance(v, tuple) else (v,))
                    if hasattr(x, "shape")]
            seen.append(nbytes(*flat))
            return out
        monkeypatch.setattr(ops, name, wrapped)

    for name in ("hash_join_probe", "segmented_aggregate", "segment_topk"):
        hook(name, getattr(ops, name))
    ref = reference.Reference(cfg, rec)
    gen = traffic.Generator(spec.traffic("analytic"), cfg, seed=5)
    done = set()
    for r in itertools.islice(iter(gen), 64):
        if r.name in done:
            continue
        done.add(r.name)
        svc = QueryService(db, ExecConfig(use_pallas_join=True))
        seen.clear()
        svc.execute(r.text).rows()
        real = cfg["num_partitions"] * sum(seen)
        logical = work.request_bytes(ref, r.name, r.args)
        assert 0 < logical <= real, (r.name, logical, real)
    assert len(done) == 8
