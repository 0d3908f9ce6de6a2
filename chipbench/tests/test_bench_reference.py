"""The benchmark's numpy reference agrees with the tree-walking oracle
``SaxonLike`` on a small seeded archive, for every template of every
mix; the comparison flags a changed answer; and the control (the
reference in bfloat16) reads as not correct."""
import itertools

import pytest

from chipbench import reference, spec, traffic
from repro.core.baselines import SaxonLike

MIXES = ("analytic", "scan")


def _cases(cfg):
    out = []
    for mix in MIXES:
        gen = traffic.Generator(spec.traffic(mix), cfg, seed=7)
        seen = {}
        for r in itertools.islice(iter(gen), 200):
            if len(seen.setdefault(r.name, [])) < 2:
                seen[r.name].append(r)
        out += [r for rs in seen.values() for r in rs]
    return out


def test_every_template_is_covered(cfg):
    assert {r.name for r in _cases(cfg)} == set(reference.KIND)


def test_reference_matches_saxon(cfg, rec, db):
    ref = reference.Reference(cfg, rec)
    sx = SaxonLike(db)
    for r in _cases(cfg):
        want = reference.normalize(r.name, sx.run_rows(r.text))
        exact, rel = reference.compare(r.name, ref.answer(r.name, r.args), want)
        assert exact and rel < 1e-6, (r.name, r.args)


def test_compare_flags_changes(cfg, rec):
    ref = reference.Reference(cfg, rec)
    rows = ref.answer("Q6", {"datatype": "TMAX", "year": 2000})
    assert reference.compare("Q6", rows[1:], rows) == (False, 0.0)
    g = ref.answer("Q12", {"datatype": "PRCP", "year": 2000})
    bad = [(g[0][0], g[0][1] + 1) + g[0][2:]] + g[1:]
    assert not reference.compare("Q12", bad, g)[0]
    top = ref.answer("Q11", {"datatype": "TMAX", "k": 3})
    assert not reference.compare("Q11", top[::-1], top)[0]
    q9 = ref.answer("Q9", {"datatype": "TMAX"})
    assert reference.compare("Q9", q9, q9) == (True, 0.0)
    x = ref.answer("Q3", {"station": "GHCND:USW00014771", "datatype": "PRCP",
                          "year": 2000})
    assert reference.compare("Q3", x * (1 + 1e-5), x)[1] == pytest.approx(1e-5)


@pytest.mark.parametrize("mix", MIXES)
def test_bfloat16_control_is_not_correct(cfg, rec, mix):
    """The control fails the comparison that decides ``correct``: a
    wrong answer, or a relative gap far above the cell's limit."""
    ref = reference.Reference(cfg, rec)
    ctl = reference.Reference(cfg, rec, precision="bfloat16")
    limit = spec.limits(f"ghcnd-1m.{mix}")["max_rel_err"]
    gen = traffic.Generator(spec.traffic(mix), cfg, seed=11)
    wrong, rel = 0, 0.0
    for r in itertools.islice(iter(gen), 64):
        exact, g = reference.compare(r.name, ctl.answer(r.name, r.args),
                                     ref.answer(r.name, r.args))
        wrong += not exact
        rel = max(rel, g)
    assert wrong > 0 or rel > 100 * limit
