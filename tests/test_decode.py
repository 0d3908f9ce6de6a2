"""Host decode: ``ResultSet.rows()`` decodes by column; it must give
exactly the rows of the per-cell decode (every slot of every partition,
one value at a time, nodes through ``node_fingerprint``): the same
python values of the same types, in the same order."""
import numpy as np
import pytest

from repro.core import QueryService, algebra as A, xdm
from repro.core.executor import ResultSet, node_fingerprint
from repro.core.queries import ALL
from repro.core.workload import q2_variant

P, T = 2, 6
KINDS = ("node", "xnode", "det", "num", "str", "date", "bool")
PATTERNS = ("empty", "full", "one_partition", "mixed")

# uneven nesting and attributes; two partitions of different shapes
DOCS = (
    ['<lib id="a1"><shelf n="1"><book lang="en"><t>Alpha</t>'
     '<y>1999</y></book><book><t>Beta</t><note>x<b>bold</b></note>'
     '</book></shelf><empty/><shelf n="2"/></lib>',
     '<lib><book><t>Gamma</t><p><p><p>deep</p></p></p></book></lib>'],
    ['<r a="1" b="2"><c>3</c><c><d e="4"/></c>tail</r>'],
)


def _cell_value(rs, v, p, r):
    kind, table = rs.schema[v]
    data = rs.raw[f"var{v}"]
    if kind == "node":
        return node_fingerprint(rs.db, table, p, int(data[p, r]))
    if kind == "xnode":
        return node_fingerprint(rs.db, table, int(data[0][p, r]),
                                int(data[1][p, r]))
    if kind == "det":
        num, sid, _ = data
        s = int(sid[p, r])
        return rs.db.strings.str(s) if s >= 0 else float(num[p, r])
    if kind == "num":
        return float(data[p, r])
    if kind == "str":
        s = int(data[p, r])
        return rs.db.strings.str(s) if s >= 0 else None
    if kind == "date":
        return int(data[p, r])
    if kind == "bool":
        return bool(data[p, r])
    raise TypeError(kind)


def cell_rows(rs):
    """The per-cell decode, the reference for ``rows()``."""
    valid = np.asarray(rs.raw["valid"])
    return [tuple(_cell_value(rs, v, p, r) for v in rs.plan.vars)
            for p in range(valid.shape[0]) for r in range(valid.shape[1])
            if valid[p, r]]


def typed(rows):
    return [tuple((type(x), repr(x)) for x in row) for row in rows]


def assert_same_rows(got, want):
    assert typed(got) == typed(want)


@pytest.fixture(scope="module")
def xml_db():
    db = xdm.Database()
    tables = []
    for docs in DOCS:
        sh = xdm.Shredder(db.names, db.strings)
        for d in docs:
            sh.shred_xml(d)
        tables.append(sh.finish())
    # two text nodes that hold only a number, as the bulk shredder
    # stores readings: an integer and one with a fraction
    t = tables[0]
    alpha_y = int(np.nonzero(t.text_sid == db.strings.lookup("1999"))[0][0])
    deep = int(np.nonzero(t.text_sid == db.strings.lookup("deep"))[0][0])
    t.text_sid[alpha_y], t.text_num[alpha_y] = -1, 1999.0
    t.text_sid[deep], t.text_num[deep] = -1, 2.25
    db.add_collection("/docs", tables)
    return db


def _result(db, kinds, valid, seed):
    """A ResultSet over synthetic ``[P, T]`` tiles, one variable per
    kind, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    parts = db.collection("/docs").partitions
    nsid = len(db.strings)
    raw = {"valid": valid, "overflow": np.zeros(P, bool)}
    schema = {}
    for v, kind in enumerate(kinds):
        if kind == "node":
            data = np.stack([rng.integers(0, parts[p].num_nodes, T)
                             for p in range(P)]).astype(np.int32)
        elif kind == "xnode":
            src = rng.integers(0, P, (P, T)).astype(np.int32)
            idx = np.vectorize(lambda q: rng.integers(
                0, parts[q].num_nodes))(src).astype(np.int32)
            data = (src, idx, np.zeros((P, T), np.float32),
                    np.full((P, T), -1, np.int32), np.zeros((P, T), np.int32))
        elif kind == "det":
            data = (rng.normal(size=(P, T)).astype(np.float32),
                    np.where(rng.random((P, T)) < 0.5, -1,
                             rng.integers(0, nsid, (P, T))).astype(np.int32),
                    np.full((P, T), -1, np.int32))
        elif kind == "num":
            data = rng.normal(scale=100, size=(P, T)).astype(np.float32)
        elif kind == "str":
            data = np.where(rng.random((P, T)) < 0.3, -1,
                            rng.integers(0, nsid, (P, T))).astype(np.int32)
        elif kind == "date":
            data = rng.integers(19000101, 20301231, (P, T)).astype(np.int32)
        else:
            data = rng.random((P, T)) < 0.5
        raw[f"var{v}"] = data
        schema[v] = (kind, "/docs" if kind in ("node", "xnode") else None)
    plan = A.DistributeResult(tuple(range(len(kinds))), A.EmptyTupleSource())
    return ResultSet(db, plan, raw, schema)


def _valid(pattern, seed):
    if pattern == "empty":
        return np.zeros((P, T), bool)
    if pattern == "full":
        return np.ones((P, T), bool)
    if pattern == "one_partition":
        valid = np.zeros((P, T), bool)
        valid[1] = True
        return valid
    return np.random.default_rng(seed).random((P, T)) < 0.5


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("kind", KINDS + ("all",))
def test_columns_equal_the_per_cell_decode(xml_db, kind, pattern):
    kinds = KINDS if kind == "all" else (kind,)
    seed = KINDS.index(kind) if kind in KINDS else 99
    rs = _result(xml_db, kinds, _valid(pattern, seed), seed)
    got = rs.rows()
    assert len(got) == int(_valid(pattern, seed).sum())
    assert_same_rows(got, cell_rows(rs))


def test_xnode_columns_cross_partitions(xml_db):
    rs = _result(xml_db, ("xnode",), np.ones((P, T), bool), 5)
    src = rs.raw["var0"][0]
    assert set(np.unique(src).tolist()) == {0, 1}
    assert_same_rows(rs.rows(), cell_rows(rs))


def test_det_gives_strings_and_numbers(xml_db):
    rs = _result(xml_db, ("det",), np.ones((P, T), bool), 2)
    rows = rs.rows()
    assert {type(r[0]) for r in rows} == {str, float}
    assert_same_rows(rows, cell_rows(rs))


def test_str_without_sid_is_none(xml_db):
    rs = _result(xml_db, ("str",), np.ones((P, T), bool), 4)
    rs.raw["var0"][0, 0] = -1
    rows = rs.rows()
    assert rows[0] == (None,)
    assert_same_rows(rows, cell_rows(rs))


def test_out_of_range_node_is_invalid(xml_db):
    rs = _result(xml_db, ("node", "xnode"), np.ones((P, T), bool), 7)
    n0 = xml_db.collection("/docs").partitions[0].num_nodes
    rs.raw["var0"][0, :3] = (-1, n0, n0 + 5)
    rs.raw["var1"][1][1, :2] = (-3, 10 ** 6)
    rows = rs.rows()
    assert [r[0] for r in rows[:3]] == ["<invalid>"] * 3
    assert [r[1] for r in rows[T:T + 2]] == ["<invalid>"] * 2
    assert_same_rows(rows, cell_rows(rs))


def test_numeric_only_text(xml_db):
    t = xml_db.collection("/docs").partitions[0]
    num_only = np.nonzero((t.text_sid < 0) & ~np.isnan(t.text_num))[0]
    book = int(t.parent[num_only[0]])
    rs = _result(xml_db, ("node",), np.zeros((P, T), bool), 0)
    rs.raw["var0"][0, :4] = (*num_only, book, 0)
    rs.raw["valid"][0, :4] = True
    rows = rs.rows()
    assert [r[0] for r in rows] == [
        "1999", "2.2", "en|Alpha|1999",
        "a1|1|en|Alpha|1999|Beta|x|bold|2"]
    assert_same_rows(rows, cell_rows(rs))


def test_string_array_follows_the_dictionary():
    sd = xdm.StringDict()
    sd.id("a")
    assert sd.array().tolist() == ["a"]
    sd.id("b")
    assert sd.array().tolist() == ["a", "b"]


def _generic_walk(t, idx):
    """``node_fingerprint``'s descendant walk."""
    desc, parents = [idx], {idx}
    for j in range(idx + 1, t.num_nodes):
        par = int(t.parent[j])
        if par in parents:
            parents.add(j)
            desc.append(j)
        elif par < idx:
            break
    return desc


@pytest.mark.parametrize("which", ("weather", "xml"))
def test_subtree_extents_equal_the_generic_walk(which, weather_db_small,
                                                xml_db):
    db = weather_db_small if which == "weather" else xml_db
    n = 0
    for coll in db.collections.values():
        for t in coll.partitions:
            size = t.subtree_size
            for i in range(t.num_nodes):
                assert _generic_walk(t, i) == list(range(i, i + size[i]))
            n += t.num_nodes
    assert n > 0


@pytest.fixture(scope="module")
def svc(weather_db):
    return QueryService(weather_db)


@pytest.mark.parametrize("name", sorted(ALL))
def test_query_rows_equal_the_per_cell_decode(svc, name):
    rs = svc.execute(ALL[name])
    assert_same_rows(rs.rows(), cell_rows(rs))


def test_ordered_rows_keep_their_order(svc):
    rs = svc.execute(ALL["Q11"])
    rows = rs.rows()
    assert len(rows) == 3
    assert [r[2] for r in rows] == sorted((r[2] for r in rows), reverse=True)
    assert rows == cell_rows(rs)


def test_batch_results_sharing_one_fetch(svc):
    rss = svc.execute_batch([q2_variant(t, 100.0)
                             for t in ("TMAX", "PRCP", "AWND")])
    assert len({rs.fetch_bytes for rs in rss}) == 1
    for rs in rss:
        rows = rs.rows()
        assert rows
        assert_same_rows(rows, cell_rows(rs))


def test_node_rows_decoded_counts_a_result_once(weather_db_small):
    svc = QueryService(weather_db_small)
    rs = svc.execute(ALL["Q2"])
    scalar = svc.execute(ALL["Q4"])
    snap = svc.stats.snapshot()
    first = rs.rows()
    assert rs.rows() == first               # decoding again counts nothing
    scalar.rows()
    d = svc.stats.diff(snap)
    assert d.node_rows_decoded == len(first) > 0
    assert d.rows_decoded == len(first) + 1
