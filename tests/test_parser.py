"""XQuery parser unit tests."""
import pytest

from repro.core import xqparser as xq
from repro.core.queries import ALL


def test_flwor_multi_for_where_return():
    ast = xq.parse('for $a in collection("/x")/r/s '
                   'for $b in collection("/y")/t '
                   'where $a/k eq $b/k return ($a, $b/v)')
    assert isinstance(ast, xq.Flwor)
    kinds = [c[0] for c in ast.clauses]
    assert kinds == ["for", "for", "where"]
    assert isinstance(ast.ret, xq.Seq) and len(ast.ret.items) == 2


def test_let_and_arithmetic_precedence():
    ast = xq.parse('for $r in collection("/s")/a let $x := '
                   'decimal(data($r/v)) where $x gt 1 + 2 * 3 '
                   'return $r')
    where = [c for c in ast.clauses if c[0] == "where"][0][1]
    assert isinstance(where, xq.Bin) and where.op == "gt"
    rhs = where.right
    assert rhs.op == "add"
    assert rhs.right.op == "mul"


def test_some_satisfies():
    ast = xq.parse('some $x in $s/labels satisfies ($x/t eq "ST" and '
                   '$x/u eq "V")')
    assert isinstance(ast, xq.SomeQ)
    assert ast.var == "x"
    assert isinstance(ast.cond, xq.Bin) and ast.cond.op == "and"


def test_hyphenated_function_names():
    ast = xq.parse('year-from-dateTime(dateTime(data($r/date))) eq 1999')
    assert isinstance(ast, xq.Bin)
    assert ast.left.name == "year-from-dateTime"


def test_path_steps_chain():
    ast = xq.parse('doc("b.xml")/bookstore/book/title')
    assert isinstance(ast, xq.Path)
    assert ast.steps == ("bookstore", "book", "title")


def test_string_literals_both_quotes():
    a = xq.parse('"double"')
    b = xq.parse("'single'")
    assert a.value == "double" and b.value == "single"


def test_numbers():
    assert xq.parse("491.744").typ == "double"
    assert xq.parse("10").typ == "integer"


def test_agg_over_flwor_div():
    ast = xq.parse('sum( for $r in collection("/s")/a return $r/v ) '
                   'div 10')
    assert isinstance(ast, xq.Bin) and ast.op == "div"
    assert isinstance(ast.left, xq.Fn) and ast.left.name == "sum"
    assert isinstance(ast.left.args[0], xq.Flwor)


def test_syntax_errors():
    for bad in ["for $x in", "collection(", "$", 'where x', "a b c ("]:
        with pytest.raises(SyntaxError):
            xq.parse(bad)


@pytest.mark.parametrize("name", list(ALL))
def test_paper_queries_all_parse(name):
    ast = xq.parse(ALL[name])
    assert ast is not None, name
