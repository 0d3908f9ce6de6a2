"""The numpy reference that checks the served path on the chip
(``repro.data.reference``) agrees with the tree-walking oracle
``SaxonLike`` on the tests' small archive — for every canonical query
and for every constant variant the chip smoke serves."""
import pytest
from conftest import WEATHER_SPEC

from repro.core.baselines import SaxonLike
from repro.core.queries import ALL
from repro.data import reference as R


@pytest.fixture(scope="module")
def ref():
    return R.Reference(WEATHER_SPEC)       # the spec of ``weather_db``


CASES = [(name, None) for name in ALL] + list(R.VARIANTS)


@pytest.mark.parametrize("name,args", CASES,
                         ids=[f"{n}-{a}" for n, a in CASES])
def test_reference_matches_saxon(weather_db, ref, name, args):
    want = R.normalize(name, SaxonLike(weather_db).run_rows(
        R.query_text(name, args)))
    got = ref.answer(name, args)
    assert R.mismatch(name, got, want) == ""
    if R.KIND[name] in ("rows", "grouped", "ordered"):
        assert got, "a case with no rows checks nothing"


def test_mismatch_reports_differences(ref):
    """The comparison is not vacuous: each kind flags a changed value."""
    q3 = ref.answer("Q3")
    assert R.mismatch("Q3", q3 * (1 + 1e-5), q3)
    rows = ref.answer("Q2")
    assert R.mismatch("Q2", rows[1:], rows)
    g = ref.answer("Q12")
    bad = [(g[0][0], g[0][1] + 1) + g[0][2:]] + g[1:]
    assert R.mismatch("Q12", bad, g)
    top = ref.answer("Q11")
    assert R.mismatch("Q11", top[::-1], top)
    assert R.mismatch("Q9", ref.answer("Q9"), ref.answer("Q9")) == ""
