"""The generator: the same seed gives the same requests, every seed the
same amounts of work in another order, and warm-up covers each plan."""
import collections
import itertools

from chipbench import reference, spec, traffic


def _take(cfg, mix, seed, n):
    return list(itertools.islice(iter(traffic.Generator(spec.traffic(mix),
                                                        cfg, seed)), n))


def test_same_seed_same_requests(cfg):
    assert _take(cfg, "analytic", 2**31 + 5, 50) == _take(cfg, "analytic",
                                                          2**31 + 5, 50)
    assert _take(cfg, "scan", 1, 50) != _take(cfg, "scan", 2, 50)


def test_every_seed_gets_the_same_work(cfg):
    """Within whole rounds of every deck, the multiset of templates and
    of Q2 thresholds does not depend on the seed."""
    n = 4 * 16 * 5                 # templates x strata x types
    runs = [_take(cfg, "scan", s, n) for s in (3, 4)]
    counts = [collections.Counter(r.name for r in rs) for rs in runs]
    assert counts[0] == counts[1] == {q: n // 4 for q in ("Q1", "Q2", "Q3", "Q4")}
    thresholds = [sorted(r.args["threshold"] for r in rs if r.name == "Q2")
                  for rs in runs]
    assert thresholds[0] != [] and len(thresholds[0]) == len(thresholds[1])


def test_values_are_present_in_the_archive(cfg):
    dom = traffic.domains(cfg)
    for r in _take(cfg, "analytic", 9, 200):
        if "datatype" in r.args:
            assert r.args["datatype"] in cfg["datatypes"]
        if "year" in r.args:
            assert r.args["year"] in dom["years"]
        if r.name == "Q11":
            assert 1 <= r.args["k"] <= 10
        assert "{" not in r.text


def test_warm_set_covers_structural_values(cfg, rec):
    gen = traffic.Generator(spec.traffic("analytic"), cfg, 21)
    first = _take(cfg, "analytic", 21, 20)
    warm = gen.warm_set(reference.Sizes(cfg, rec).size)
    assert sorted(r.args["k"] for r in warm if r.name == "Q11") == list(range(1, 11))
    assert {r.name for r in warm} == {t["name"] for t in
                                      spec.traffic("analytic")["templates"]}
    # drawing the warm set leaves the window's stream as it was
    assert list(itertools.islice(iter(gen), 20)) == first
