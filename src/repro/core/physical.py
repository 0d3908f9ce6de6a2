"""Physical operators: the logical plan as pure JAX over columnar tiles.

This is the Hyracks layer re-thought for TPU (DESIGN.md §2): instead of
push-based frames and per-record virtual dispatch, every operator is a
pure function over a fixed-capacity **Tile** (columns + validity mask),
and the whole plan fuses into one XLA program. Partitioned parallelism
comes from running the compiled local function under ``vmap`` (cluster
simulation on one device) or ``shard_map`` (real SPMD) over the mesh's
``data`` axis with ``lax`` collectives at the exchange points the
rewrite rules introduced:

  two-step AGGREGATE  -> local masked reduce + psum / all_gather-min
  hash JOIN           -> build-side all_gather ("hybrid hash", build
                         resident) or hash-mod all_to_all repartition
                         ("grace", the mrql_like baseline)
  DISTRIBUTE-RESULT   -> per-shard tiles, host concatenation

Cardinality changes (DATASCAN, UNNEST) produce fixed-capacity index
tiles via ``rows_from_mask`` with an overflow flag — the moral
equivalent of Hyracks' frame-size limit, surfaced instead of crashed.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algebra as A
from repro.core import xdm

I32 = jnp.int32
F32 = jnp.float32
NEG = -1


# ---------------------------------------------------------------------------
# Device-side table bundle
# ---------------------------------------------------------------------------

def device_tables(db: xdm.Database) -> dict:
    """Pack a Database into arrays: {collection: {col: [P, ...]}} plus
    shared per-sid derived arrays."""
    out: dict[str, Any] = {"__derived__": {
        k: jnp.asarray(v) for k, v in db.derived().items()}}
    for name, coll in db.collections.items():
        t = coll.padded()
        out[name] = {
            "kind": jnp.asarray(t.kind), "name": jnp.asarray(t.name),
            "parent": jnp.asarray(t.parent),
            "text_sid": jnp.asarray(t.text_sid),
            "text_num": jnp.asarray(t.text_num),
            "text_date": jnp.asarray(t.text_date),
            "field_map": jnp.asarray(t.field_map),
            "multi": {k: jnp.asarray(v) for k, v in t.multi.items()},
        }
    return out


def _gather(arr, idx, fill):
    """Safe gather: idx < 0 -> fill."""
    safe = jnp.clip(idx, 0, arr.shape[0] - 1)
    val = jnp.take(arr, safe, axis=0)
    mask = (idx >= 0)
    if val.ndim > mask.ndim:
        mask = mask[..., None]
    return jnp.where(mask, val, fill)


# ---------------------------------------------------------------------------
# Columns and tiles
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Col:
    """One tile column. ``kind`` is static:
      node  data=int32 row index into ``table``'s node arrays
      atom  data=int32 node index (value not yet projected)
      num / str / date / bool   projected values
      det   detached atom: data=(num, sid, date) triple
      xnode cross-partition node: data=(part, idx, num, sid, date) —
            the "serialized node" of a Hyracks exchange; host-side
            result extraction dereferences (part, idx)
    """
    kind: str
    data: Any
    table: Optional[str] = None

    def shape(self):
        d = self.data[0] if self.kind in ("det", "xnode") else self.data
        return d.shape


@dataclasses.dataclass
class Tile:
    cols: dict[int, Col]
    valid: jnp.ndarray          # bool [T]
    overflow: jnp.ndarray      # bool scalar — capacity exceeded anywhere


def _const_col(value, like_shape) -> Col:
    return Col("const", value)


# ---------------------------------------------------------------------------
# Expression compiler
# ---------------------------------------------------------------------------

class ExprEval:
    """Vectorized evaluator for scalar expressions over a tile.

    Compile-time context: the host Database (dictionary lookups for
    string constants and element names) + device tables.
    """

    def __init__(self, db: xdm.Database, tables: dict, params: tuple = ()):
        self.db = db
        self.tables = tables
        # prepared-query parameter vector: traced scalars (one per
        # algebra.Param slot), so a binding change is a new input, not
        # a new compilation
        self.params = params

    # -- atom projections
    def _tab(self, col: Col) -> dict:
        assert col.table is not None, "node column lost its table"
        return self.tables[col.table]

    def atom_num(self, col: Col) -> jnp.ndarray:
        if col.kind == "num":
            return col.data
        if col.kind == "date":
            return col.data.astype(F32)
        if col.kind in ("node", "atom"):
            return _gather(self._tab(col)["text_num"], col.data, jnp.nan)
        if col.kind == "det":
            return col.data[0]
        if col.kind == "xnode":
            return col.data[2]
        if col.kind == "const":
            return col.data
        raise TypeError(col.kind)

    def atom_sid(self, col: Col) -> jnp.ndarray:
        if col.kind == "str":
            return col.data
        if col.kind in ("node", "atom"):
            return _gather(self._tab(col)["text_sid"], col.data, NEG)
        if col.kind == "det":
            return col.data[1]
        if col.kind == "xnode":
            return col.data[3]
        raise TypeError(col.kind)

    def atom_date(self, col: Col) -> jnp.ndarray:
        if col.kind == "date":
            return col.data
        if col.kind in ("node", "atom"):
            return _gather(self._tab(col)["text_date"], col.data, NEG)
        if col.kind == "det":
            return col.data[2]
        if col.kind == "xnode":
            return col.data[4]
        raise TypeError(col.kind)

    def detach(self, col: Col) -> Col:
        """Materialize to a (num, sid, date) triple — required before a
        column crosses a partition-exchange boundary (join/gather)."""
        if col.kind in ("det", "xnode"):
            return col
        return Col("det", (self.atom_num(col), self.atom_sid(col),
                           self.atom_date(col)))

    def to_xnode(self, col: Col, part_index) -> Col:
        """Serialize a node column for a partition exchange: carry the
        (origin partition, node index) reference plus the projected
        atoms — the analogue of Hyracks serializing the XDM subtree
        into the connector frame."""
        if col.kind not in ("node", "atom"):
            return col
        part = jnp.full(col.data.shape, part_index, I32)
        return Col("xnode", (part, col.data, self.atom_num(col),
                             self.atom_sid(col), self.atom_date(col)),
                   col.table)

    # -- comparisons
    def _cmp(self, fn: str, a: Col, b: Col) -> Col:
        ops = {"value-eq": jnp.equal, "value-ne": jnp.not_equal,
               "value-lt": jnp.less, "value-le": jnp.less_equal,
               "value-gt": jnp.greater, "value-ge": jnp.greater_equal,
               "algebricks-eq": jnp.equal}
        op = ops[fn]
        # choose comparison domain by static kinds
        if "str" in (a.kind, b.kind):
            return Col("bool", op(self.atom_sid(a), self.atom_sid(b)))
        if "date" in (a.kind, b.kind):
            return Col("bool", op(self.atom_date(a), self.atom_date(b)))
        if "num" in (a.kind, b.kind) or "const" in (a.kind, b.kind):
            return Col("bool", op(self.atom_num(a), self.atom_num(b)))
        # both atoms/dets: string-compare when both have sids, else num
        sa, sb = self.atom_sid(a), self.atom_sid(b)
        both_str = (sa >= 0) & (sb >= 0)
        r_str = op(sa, sb)
        r_num = op(self.atom_num(a), self.atom_num(b))
        return Col("bool", jnp.where(both_str, r_str, r_num))

    def const(self, c: A.Const) -> Col:
        if c.typ == "string":
            sid = self.db.strings.lookup(str(c.value))
            if sid < 0:
                sid = -3   # absent: matches nothing
            return Col("str", jnp.int32(sid))
        if c.typ in ("double", "integer"):
            return Col("const", jnp.float32(c.value))
        if c.typ == "boolean":
            return Col("bool", jnp.bool_(c.value == "true"))
        raise TypeError(c)

    def param(self, e: A.Param) -> Col:
        p = self.params[e.idx]
        if e.typ == "str":
            return Col("str", p)
        if e.typ == "num":
            return Col("const", p)
        if e.typ == "date":
            return Col("date", p)
        raise TypeError(e.typ)

    def eval(self, e: A.Expr, env: dict[int, Col]) -> Col:
        if isinstance(e, A.Const):
            return self.const(e)
        if isinstance(e, A.Param):
            return self.param(e)
        if isinstance(e, A.Var):
            return env[e.n]
        if isinstance(e, A.Some):
            return self.eval_some(e, env)
        assert isinstance(e, A.Call), e
        fn = e.fn
        if fn in ("treat", "promote", "boolean",
                  "sort-distinct-nodes-asc-or-atomics",
                  "sort-nodes-asc-or-atomics",
                  "distinct-nodes-or-atomics"):
            # no-ops on this representation: masks/row-order already
            # encode document order & distinctness; EBV of bool is id
            return self.eval(e.args[0], env)
        if fn == "child":
            base = self.eval(e.args[0], env)
            assert base.kind in ("node", "atom"), base.kind
            nm = str(e.args[1].value)
            f = self.db.names.lookup(nm)
            fm = self._tab(base)["field_map"]
            idx = _gather(fm, base.data, NEG)
            child_idx = idx[..., f] if f >= 0 else jnp.full_like(
                base.data, NEG)
            return Col("node", child_idx, base.table)
        if fn == "data":
            base = self.eval(e.args[0], env)
            if base.kind in ("node", "atom"):
                return Col("atom", base.data, base.table)
            return base
        if fn == "decimal":
            return Col("num", self.atom_num(self.eval(e.args[0], env)))
        if fn == "string":
            return Col("str", self.atom_sid(self.eval(e.args[0], env)))
        if fn == "dateTime":
            a = e.args[0]
            if isinstance(a, A.Const):       # dateTime("1976-07-04T..")
                m = xdm._DATE_RE.match(str(a.value))
                assert m, a
                packed = xdm.pack_date(int(m.group(1)), int(m.group(2)),
                                       int(m.group(3)))
                return Col("date", jnp.int32(packed))
            base = self.eval(a, env)
            if base.kind in ("node", "atom"):
                return Col("date", self.atom_date(base))
            if base.kind == "str":
                der = self.tables["__derived__"]["date_of_sid"]
                return Col("date", _gather(der, base.data, NEG))
            return Col("date", base.data.astype(I32))
        if fn == "year-from-dateTime":
            d = self.eval(e.args[0], env)
            return Col("num", (self.atom_date(d) // 10000).astype(F32))
        if fn == "month-from-dateTime":
            d = self.eval(e.args[0], env)
            return Col("num",
                       (self.atom_date(d) // 100 % 100).astype(F32))
        if fn == "day-from-dateTime":
            d = self.eval(e.args[0], env)
            return Col("num", (self.atom_date(d) % 100).astype(F32))
        if fn == "upper-case":
            s = self.eval(e.args[0], env)
            der = self.tables["__derived__"]["ucase_sid"]
            return Col("str", _gather(der, self.atom_sid(s), NEG))
        if fn in ("value-eq", "value-ne", "value-lt", "value-le",
                  "value-gt", "value-ge", "algebricks-eq"):
            return self._cmp(fn, self.eval(e.args[0], env),
                             self.eval(e.args[1], env))
        if fn in ("and", "or"):
            a = self.eval(e.args[0], env).data
            b = self.eval(e.args[1], env).data
            return Col("bool", (a & b) if fn == "and" else (a | b))
        if fn == "not":
            return Col("bool", ~self.eval(e.args[0], env).data)
        if fn in ("add", "subtract", "multiply", "divide"):
            a = self.atom_num(self.eval(e.args[0], env))
            b = self.atom_num(self.eval(e.args[1], env))
            if fn == "divide" and isinstance(e.args[1], A.Param):
                # XLA strength-reduces division by a compile-time
                # constant into multiplication by its reciprocal;
                # mirror that for a lifted parameter so prepared
                # execution stays bit-identical to the baked plan
                return Col("num", a * (1.0 / b))
            op = {"add": jnp.add, "subtract": jnp.subtract,
                  "multiply": jnp.multiply,
                  "divide": jnp.divide}[fn]
            return Col("num", op(a, b))
        if fn == "iterate":
            # singleton pass-through (the executor handles sequence
            # unnesting at the operator level)
            return self.eval(e.args[0], env)
        raise NotImplementedError(fn)

    def eval_some(self, e: A.Some, env: dict[int, Col]) -> Col:
        """Quantified expression over a repeated child field: evaluate
        the condition on the [T, W] expansion and OR-reduce."""
        got = self._multi_source(e.source, env)
        assert got is not None, f"some: unsupported source {e.source}"
        base, nm = got
        tab = self._tab(base)
        assert nm in tab["multi"], (
            f"collection {base.table!r} lacks a repeated-field index for "
            f"{nm!r}; add it to multi_names at shred time")
        mm = tab["multi"][nm]                       # [N, W]
        kids = _gather(mm, base.data, NEG)          # [T, W]
        kid_col = Col("node", kids, base.table)
        cond = self.eval(e.cond, {**env, e.var: kid_col})
        ok = cond.data & (kids >= 0)
        return Col("bool", jnp.any(ok, axis=-1))

    def _multi_source(self, e: A.Expr, env: dict[int, Col]
                      ) -> Optional[tuple[Col, str]]:
        """child(treat($v,..), "name") -> (eval($v), "name")."""
        if isinstance(e, A.Call) and e.fn == "child":
            inner, nm = e.args
            if isinstance(inner, A.Call) and inner.fn == "treat":
                inner = inner.args[0]
            base = self.eval(inner, env)
            return base, str(nm.value)
        if isinstance(e, A.Var):
            col = env[e.n]
            return None if col.kind != "node" else None
        return None


# ---------------------------------------------------------------------------
# Path matching (DATASCAN / UNNEST-child machinery)
# ---------------------------------------------------------------------------

def path_match_mask(tab: dict, names: xdm.NameDict,
                    steps: tuple[str, ...]) -> jnp.ndarray:
    """Vectorized child-path evaluation over the node table: mask of
    nodes matching /step1/step2/... from the document roots."""
    kind, name, parent = tab["kind"], tab["name"], tab["parent"]
    frontier = kind == xdm.DOCUMENT
    for s in steps:
        f = names.lookup(s)
        up = _gather(frontier, parent, False)
        frontier = up & (name == (f if f >= 0 else -99))
    return frontier


def round_cap(n: int, multiple: int = 16) -> int:
    """Round a capacity up to an alignment multiple. Bucketing caps
    keeps the number of distinct compiled shapes (and therefore plan-
    cache entries) small as estimates drift."""
    n = max(int(n), multiple)
    return ((n + multiple - 1) // multiple) * multiple


def estimate_scan_cap(db: xdm.Database, collection: str,
                      steps: tuple[str, ...]) -> Optional[int]:
    """Statistics-based per-partition capacity for a DATASCAN/UNNEST of
    ``/step1/step2/...`` over ``collection``: the build-time per-tag
    count is an exact upper bound for child-path matches (every match
    is a node with the path's final tag). None when no stats exist."""
    stats = getattr(db, "stats", {}).get(collection)
    if stats is None:
        return None
    bound = stats.path_match_bound(db.names, tuple(steps))
    if bound is None:
        return None
    return round_cap(bound)


def estimate_group_cap(db: xdm.Database, tag: str) -> Optional[int]:
    """Statistics-based segment capacity for a GROUP-BY whose key is
    drawn from ``.../tag`` children: the build-time global distinct-
    value count is an exact upper bound on the number of groups. Maxed
    over collections (the key expression alone does not always name
    its source collection); None when no statistics exist."""
    stats = getattr(db, "stats", {})
    if not stats:
        return None
    bounds = [s.group_key_bound(db.names, tag) for s in stats.values()]
    return round_cap(max(bounds))


def estimate_topk_cap(db: xdm.Database, tag: str,
                      k: Optional[int]) -> Optional[int]:
    """Statistics-based ordered-output capacity for an ORDER BY /
    LIMIT over a GROUP-BY on ``.../tag`` keys: the sorted tile never
    needs more rows than min(limit k, distinct group keys) — the same
    ``tag_distinct`` bound that presizes the segment space, clipped by
    the top-k pushdown. None when no statistics exist and no limit is
    given (the full segment width then keeps results exact)."""
    bound = estimate_group_cap(db, tag)
    if k is not None:
        cap = round_cap(k)
        return min(cap, bound) if bound is not None else cap
    return bound


#: lanes per block of the TPU compaction (one vreg row)
COMPACT_BLOCK = 128


def rows_from_mask(mask: jnp.ndarray, cap: int
                   ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """mask [N] -> (idx [cap], valid [cap], overflow). Row order is
    node-table order == document order (rule 4.1.1's free sort).

    ``idx[j]`` is the position of the (j+1)-th set bit, ``NEG`` past
    the last; ``valid = arange(cap) < total``; ``overflow = total >
    cap``, with ``cap`` clipped to N. Each backend gets its own
    lowering, chosen when the program is lowered for it
    (``lax.platform_dependent``), because the two want opposite
    things:

    - TPU (``_compact_blocked``): a gather of one scalar per slot costs
      about 10 ns there, so the per-slot binary search, a loop of
      log2(N) such gathers, held most of every query's device time.
      The blocked compaction reads the mask a constant number of times
      and gathers once.
    - CPU and the rest (``_compact_search``): prefix count + binary
      search, scatter-free, where ``jnp.nonzero(size=...)``'s scatter
      lowers to a serial loop that dominated every query's warm
      latency (the ordered-suite pushdown regression).

    Both give the same bits. Either runs under the ``rows_from_mask``
    named scope, so a device profile can tell compaction time from the
    rest of the plan."""
    with jax.named_scope("rows_from_mask"):
        return jax.lax.platform_dependent(
            mask, tpu=partial(_compact_blocked, cap=cap),
            default=partial(_compact_search, cap=cap))


def _compact_search(mask: jnp.ndarray, cap: int
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The j-th slot is the first position whose running set-bit count
    reaches j+1: a binary search of the prefix count per slot."""
    cap = min(cap, mask.shape[0])
    pos = jnp.cumsum(mask.astype(I32))
    total = pos[-1]
    idx = jnp.searchsorted(pos, jnp.arange(1, cap + 1, dtype=I32))
    valid = jnp.arange(cap) < total
    return jnp.where(valid, idx, NEG).astype(I32), valid, total > cap


def _compact_blocked(mask: jnp.ndarray, cap: int
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Two-level compaction over ``COMPACT_BLOCK``-lane blocks of the
    mask.

    Inside each block, the lane of its (r+1)-th set bit is the number
    of lanes whose running count is at most r (a compare and a sum
    per lane pair, no gather). Slot j then lies in the block whose
    set bits end past j: scattering each block's width and count at
    its end (one update per block) and prefix-summing over the slots
    gives that block's offset and first slot, and one gather of a
    scalar per slot reads the position from the block table."""
    n, block = mask.shape[0], COMPACT_BLOCK
    cap = min(cap, n)
    nb = -(-n // block)
    local = jnp.cumsum(jnp.pad(mask, (0, nb * block - n))
                       .reshape(nb, block).astype(I32), axis=1)
    count = local[:, -1]
    end = jnp.cumsum(count)                     # set bits through block b
    total = end[-1]
    lane = jnp.arange(block, dtype=I32)
    first = jnp.arange(nb, dtype=I32)[:, None] * block
    table = first + jnp.sum((local[:, :, None] <= lane).astype(I32), axis=1)
    marks = jnp.zeros((cap + 1, 2), I32).at[jnp.minimum(end, cap)].add(
        jnp.stack([jnp.full((nb,), block, I32), count], axis=1),
        indices_are_sorted=True)
    before = jnp.cumsum(marks[:cap], axis=0)    # (offset, first slot)
    slot = jnp.arange(cap, dtype=I32)
    valid = slot < total
    at = jnp.where(valid, before[:, 0] + slot - before[:, 1], 0)
    idx = jnp.take(table.reshape(-1), at, indices_are_sorted=True,
                   mode="clip")
    return jnp.where(valid, idx, NEG).astype(I32), valid, total > cap


def topk_rows(sort_keys: list[tuple[jnp.ndarray, bool]],
              valid: jnp.ndarray, cap: Optional[int],
              limit: Optional[int], fused: bool = False
              ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Capacity-bounded segmented sort: the ORDER BY / top-k core.

    ``sort_keys`` are (key array [N], descending) pairs, most
    significant first; keys are numeric (i32 lexicographic string
    ranks, packed dates, or f32 aggregate values). Valid rows sort
    first by the keys; invalid rows sink to the end. Returns
    (idx [C], valid [C], overflow) with C = min(cap or N, N): the
    gather order of the sorted tile. ``limit`` masks output rows past
    the top k; ``overflow`` is raised iff the C output slots cannot
    hold every row the query needs — min(#valid, limit) — so a
    top-k pushdown (cap ~ k) never materializes the full segment
    space, and a too-small cap surfaces on its own regrowth flag
    instead of silently truncating the ranking.

    ``fused=True`` routes the selection through the segment top-k
    kernel entry point (kernels.ops.segment_topk — Pallas on TPU, its
    bit-identical jnp twin on CPU); the operand stack handed over is
    exactly the one ``jnp.lexsort`` consumes here, so the two routes
    agree index-for-index."""
    n = valid.shape[0]
    cap = n if cap is None else min(int(cap), n)
    ops = []
    for key, desc in sort_keys:
        if key.dtype == jnp.bool_:
            key = key.astype(I32)
        zero = jnp.zeros((), key.dtype)
        k = jnp.where(valid, key, zero)   # invalid rows: inert keys
        ops.append(-k if desc else k)
    flag = (~valid).astype(I32)
    if fused:
        from repro.kernels import ops as kops
        idx = kops.segment_topk((flag,) + tuple(ops), cap)
    else:
        # lexsort: LAST operand is primary — invalid-sinking flag first
        order = jnp.lexsort(tuple(reversed(ops)) + (flag,))
        idx = order[:cap].astype(I32)
    out_valid = jnp.take(valid, idx)
    if limit is not None:
        out_valid = out_valid & (jnp.arange(cap) < limit)
    n_valid = jnp.sum(valid.astype(I32))
    need = n_valid if limit is None else jnp.minimum(
        n_valid, jnp.int32(limit))
    overflow = need > cap
    return idx, out_valid, overflow
