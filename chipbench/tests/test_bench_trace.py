"""The trace reduction on events written by hand: busy union, kernel
time, idle gaps and the host span they fall in."""
import pytest

from chipbench import trace as T


def test_union_covered_gaps():
    evs = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 3.0, 4.0), ("d", 3.5, 3.6)]
    m = T.union(evs)
    assert m == [(0.0, 2.0), (3.0, 4.0)]
    assert T.covered(m, 0.0, 5.0) == 3.0
    assert T.covered(m, 1.5, 3.5) == pytest.approx(1.0)
    assert T.gaps(m, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert T.gaps(m, 0.5, 1.5) == []


def test_idle_by_span_and_totals():
    spans = [("bench.gen", 0.0, 0.1), ("bench.execute:Q5", 0.1, 2.0),
             ("bench.decode:Q5", 2.0, 3.0)]
    idle = [(0.0, 0.5), (1.5, 2.5), (3.0, 3.25)]
    got = T.idle_by_span(idle, spans)
    assert got == pytest.approx({"gen": 0.1, "execute:Q5": 0.9,
                                 "decode:Q5": 0.5, "between": 0.25})
    ops = [("k1", 0.0, 1.0), ("k2", 1.0, 1.5), ("k1", 2.0, 2.25)]
    assert T.op_totals(ops) == {"k1": 1.25, "k2": 0.5}
    assert T.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0], ["c", 2.0]]


def test_summarize_two_chips():
    spans = [("bench.gen", 0.0, 1.0), ("bench.execute:Q9", 1.0, 5.0),
             ("bench.decode:Q9", 5.0, 6.0), ("other", 0.0, 9.0)]
    ops = {"/device:TPU:0": [("fusion", 2.0, 3.0), ("kern", 3.0, 4.0),
                             ("early", -5.0, -4.0)],
           "/device:TPU:1": [("fusion", 2.5, 3.5), ("kern", 3.5, 4.0)]}
    tr = T.Trace(ops, [s for s in spans if s[0].startswith("bench.")], [])
    s = T.summarize(tr)
    assert s["window_s"] == 6.0
    assert s["busy_s"] == pytest.approx((2.0 + 1.5) / 2)
    # execute spans 4 s, of which any chip is busy 2.0 s (2.0 to 4.0)
    assert s["serve_host_s"] == pytest.approx(2.0)
    assert s["op_s"] == pytest.approx({"fusion": 1.0, "kern": 0.75})
    assert dict((k, v) for k, v in s["idle_gaps"]) == pytest.approx(
        {"gen": 1.0, "execute:Q9": 2.0, "decode:Q9": 1.0})


def test_summarize_refuses_an_empty_trace():
    with pytest.raises(RuntimeError):
        T.summarize(T.Trace({}, [("bench.gen", 0.0, 1.0)], ["x"]))
