"""The paper's worked rewrite traces (§4.1, §4.2), asserted step by
step — the structural faithfulness tests promised in DESIGN.md §4."""
import pytest

from repro.core.algebra import (Aggregate, Assign, Call, DataScan,
                                DistributeResult, Join, Select, Subplan,
                                Unnest, pretty, signature, walk)
from repro.core.queries import ALL
from repro.core.rewrite import optimize, run_rules
from repro.core.rewrite import path_rules as pr
from repro.core.rewrite import parallel_rules as rr
from repro.core.rewrite.engine import (apply_rule_once,
                                       remove_identity_assigns)
from repro.core.translator import translate

BOOKS = 'doc("books.xml")/bookstore/book'
COLL = 'collection("/books")/bookstore/book'
COUNT = 'count( for $x in collection("/books")/bookstore/book return $x )'
JOIN = '''
for $r in collection("/ann-books")/bookstore/book
for $s in collection("/joe-books")/bookstore/book
where $r/title eq $s/title
return $r
'''


def sig(plan):
    return signature(plan)


def test_initial_normalized_plan_matches_paper_books():
    """§4.1 initial plan: two sort-distinct ASSIGNs over two SUBPLANs,
    each SUBPLAN = AGGREGATE(create_sequence(child)) over
    UNNEST(iterate) over NTS, rooted at ASSIGN(doc)."""
    plan = translate(BOOKS)
    s = sig(plan)
    assert s == [
        "DistributeResult",
        "Unnest:iterate",
        "Assign:sort-distinct-nodes-asc-or-atomics",
        "Subplan",
        "Aggregate:create_sequence", "Unnest:iterate",
        "NestedTupleSource",
        "Assign:sort-distinct-nodes-asc-or-atomics",
        "Subplan",
        "Aggregate:create_sequence", "Unnest:iterate",
        "NestedTupleSource",
        "Assign:doc",
        "EmptyTupleSource",
    ]


def test_rule_411_removes_both_sorts():
    plan = translate(BOOKS)
    plan, fired = apply_rule_once(plan, pr.remove_sort_distinct)
    assert fired
    plan, fired = apply_rule_once(plan, pr.remove_sort_distinct)
    assert fired
    plan = remove_identity_assigns(plan)
    # matches the paper's plan after 4.1.1: no ASSIGN sort ops left
    assert "Assign:sort-distinct-nodes-asc-or-atomics" not in sig(plan)
    assert sig(plan).count("Subplan") == 2


def test_rule_412_removes_subplans_one_at_a_time():
    plan = translate(BOOKS)
    for _ in range(2):
        plan, _ = apply_rule_once(plan, pr.remove_sort_distinct)
    plan = remove_identity_assigns(plan)
    plan, fired = apply_rule_once(plan, pr.remove_subplan_iterate)
    assert fired, pretty(plan)
    assert sig(plan).count("Subplan") == 1   # "applied a second time"
    plan = remove_identity_assigns(plan)
    plan, fired = apply_rule_once(plan, pr.remove_subplan_iterate)
    assert fired
    assert sig(plan).count("Subplan") == 0


def test_rule_413_414_final_books_plan():
    """Final §4.1 plan: one merged UNNEST(child(child(...))) over
    UNNEST(iterate) over ASSIGN(doc)."""
    plan = optimize(translate(BOOKS))
    s = sig(plan)
    assert s == ["DistributeResult", "Unnest:child", "Unnest:iterate",
                 "Assign:doc", "EmptyTupleSource"]
    # the merged expression nests both steps (4.1.4)
    unnest = list(walk(plan))[1]
    assert str(unnest.expr).count("child(") == 2
    assert '"book"' in str(unnest.expr) and '"bookstore"' in str(unnest.expr)


def test_rule_421_datascan_with_path_pushdown():
    plan = optimize(translate(COLL))
    s = sig(plan)
    assert s == ["DistributeResult", "DataScan:/books/bookstore/book",
                 "EmptyTupleSource"]


def test_rule_422_aggregate_pushdown_and_two_step():
    plan = optimize(translate(COUNT))
    s = sig(plan)
    assert s == ["DistributeResult", "Unnest:iterate", "Subplan",
                 "Aggregate:count", "DataScan:/books/bookstore/book",
                 "NestedTupleSource", "EmptyTupleSource"]
    agg = [o for o in walk(plan) if isinstance(o, Aggregate)][0]
    assert (agg.local_fn, agg.global_fn) == ("count", "sum")


def test_rule_423_hash_join():
    plan = optimize(translate(JOIN))
    joins = [o for o in walk(plan) if isinstance(o, Join)]
    assert len(joins) == 1
    j = joins[0]
    assert j.hash_keys, "equi-condition must be hash-annotated"
    assert isinstance(j.cond, Call) and j.cond.fn == "algebricks-eq"
    # each branch: pushed-down ASSIGN over its own DATASCAN
    for side in (j.left, j.right):
        s = sig(side)
        assert s[0].startswith("Assign:child"), s
        assert any(x.startswith("DataScan:") for x in s)
    # no SELECT left above the join
    assert not any(isinstance(o, Select) for o in walk(plan))


def test_sort_weakening_variants():
    """4.1.1 also downgrades to sort-only / distinct-only forms when
    just one property is broken (lattice behaviour)."""
    from repro.core.algebra import Assign, Const, EmptyTupleSource, Var
    from repro.core.rewrite.engine import Context
    # distinct-only input: pretend var 1 is ordered but has dups
    op = Assign(2, Call("sort-distinct-nodes-asc-or-atomics",
                        (Var(1),)), EmptyTupleSource())
    ctx = Context(use={1: 1}, singleton={}, props={1: (True, False)})
    out = pr.remove_sort_distinct(op, ctx)
    assert isinstance(out.expr, Call)
    assert out.expr.fn == "distinct-nodes-or-atomics"
    ctx = Context(use={1: 1}, singleton={}, props={1: (False, True)})
    out = pr.remove_sort_distinct(op, ctx)
    assert out.expr.fn == "sort-nodes-asc-or-atomics"
    ctx = Context(use={1: 1}, singleton={}, props={1: (False, False)})
    assert pr.remove_sort_distinct(op, ctx) is None


def test_paper_trace_text_books():
    """The pretty-printed initial plan contains the paper's exact
    expression spellings."""
    txt = pretty(translate(BOOKS))
    assert 'doc(promote(data("books.xml"), string))' in txt
    assert 'create_sequence(child(treat($$' in txt
    assert 'sort-distinct-nodes-asc-or-atomics' in txt


@pytest.mark.parametrize("name", list(ALL))
def test_q_plans_all_compile(name):
    kinds = sig(optimize(translate(ALL[name])))
    assert kinds[0] == "DistributeResult"
    assert any(k.startswith("DataScan:") for k in kinds), name
