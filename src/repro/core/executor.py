"""Plan executor: logical plan -> one fused SPMD JAX function.

Execution model (DESIGN.md §2): a query compiles to a *local* function
over one partition's node tables. Partitioned parallelism is the same
function run under

  * ``jax.vmap(..., axis_name="data")``  — cluster simulation on one
    device (tests/benchmarks; collectives become batched reductions)
  * ``shard_map(..., mesh, axis "data")`` — real SPMD over the mesh
    (multi-device runs: the 4-chip cell, tests on forced host devices)

with identical ``lax`` collectives inside (psum for two-step
aggregation, all_gather for the hybrid-hash build broadcast, all_to_all
for grace-style repartition). This mirrors how a Hyracks job runs the
same operator pipeline on every node with connectors in between.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import algebra as A
from repro.core import xdm
from repro.core.errors import InvalidArgumentError
from repro.core.obs import trace as obs_trace
from repro.core.physical import (Col, ExprEval, Tile, _gather,
                                 device_tables, path_match_mask,
                                 rows_from_mask, topk_rows)

I32 = jnp.int32
F32 = jnp.float32


@dataclasses.dataclass
class ExecConfig:
    scan_cap: Optional[int] = None        # None: padded table size
    join_cap: Optional[int] = None        # probe-side output capacity
                                          # (None: uncompacted probe width)
    group_cap: Optional[int] = None       # group-by segment capacity
                                          # (None: full string dictionary)
    topk_cap: Optional[int] = None        # ordered-output capacity: the
                                          # ORDER BY / LIMIT sorted tile
                                          # width (None: the child tile's
                                          # full segment width)
    join_strategy: str = "broadcast"      # broadcast | repartition
    join_bucket: int = 4                  # hash-bucket probe width
    # Kernel-path knobs are tri-state: None defers to
    # ``resolve_kernel_policy`` at compile time (backend- and
    # plan-aware defaults, measured by the "kernels" benchmark suite);
    # True/False pins the route. ``REPRO_FORCE_JNP=1`` overrides both
    # to False (see README).
    use_pallas_join: Optional[bool] = None      # join probe kernel
    use_pallas_segments: Optional[bool] = None  # fused group-by/top-k
                                                # segment engine

    def signature(self) -> tuple:
        """Every config field in declaration order, derived from
        ``dataclasses.fields`` — a new capacity knob joins the
        plan-cache key by construction rather than by remembering to
        extend a hand-maintained tuple (the exact omission the
        cap-registry lint in core.analysis guards the rest of a knob's
        obligations against)."""
        return tuple(getattr(self, f.name)
                     for f in dataclasses.fields(self))

    def cap_key(self) -> tuple:
        """The fields that change compiled shapes/semantics — the
        plan-cache key component (service.py)."""
        return self.signature()


# Executor-side overflow-flag registry: for every capacity-bounded
# stage, the ExecConfig knob that bounds it -> the output flag that
# reports its saturation.  EvalCtx accumulation, `_outputs`, and
# ResultSet attributes are all driven from this table; the service
# regrowth ladder must have exactly one rung per entry, capacity-flow
# analysis (core.analysis.capflow) checks plans against it, and the
# cap-registry lint (core.analysis.lint) statically cross-checks that
# all four layers stay in sync.
OVERFLOW_FLAGS: dict[str, str] = {
    "scan_cap": "overflow_scan",
    "join_bucket": "overflow_join",
    "join_cap": "overflow_join_cap",
    "group_cap": "overflow_group_cap",
    "topk_cap": "overflow_topk_cap",
}


def resolve_kernel_policy(plan: A.Op, cfg: ExecConfig) -> ExecConfig:
    """Resolve the tri-state kernel knobs for one compilation.

    Defaults encode the measured winners of the kernels benchmark
    suite (benchmarks/serving_benchmarks.py --suite kernels), which
    gates them against a fresh sweep on every full run:

    * ``use_pallas_segments``: True — the fused segment engine (one
      pass: key-dictionary build, segment-id mapping, reduce, top-k
      selection) is scatter-free, so on XLA CPU it avoids the serial
      while-loops that scatter/unique lower to, and on TPU it is the
      Pallas kernel family. The one exception is a plan that sorts at
      *full width* (an OrderBy with ``topk_cap=None``): that is a
      whole-segment-space sort, outside the bounded-tile contract of
      the selection kernel, so it keeps the legacy lexsort path.
    * ``use_pallas_join``: True only on TPU. On CPU the interpreted
      Pallas probe is orders of magnitude slower than the sorted-hash
      jnp probe at every cap size in the sweep.

    ``REPRO_FORCE_JNP=1`` pins both knobs False — the operational
    escape hatch (README): every operator falls back to the pure-jnp
    reference implementations.

    Pure function of (plan, cfg, environment); never mutates ``cfg``
    (configs are shared cache keys in the service layer)."""
    if os.environ.get("REPRO_FORCE_JNP") == "1":
        return dataclasses.replace(cfg, use_pallas_segments=False,
                                   use_pallas_join=False)
    seg, join = cfg.use_pallas_segments, cfg.use_pallas_join
    if join is None:
        join = jax.default_backend() == "tpu"
    if seg is None:
        full_width_sort = cfg.topk_cap is None and any(
            isinstance(op, A.OrderBy) for op in A.walk(plan))
        seg = not full_width_sort
    if seg == cfg.use_pallas_segments and join == cfg.use_pallas_join:
        return cfg
    return dataclasses.replace(cfg, use_pallas_segments=seg,
                               use_pallas_join=join)


def example_params(param_specs: tuple,
                   batch: Optional[int] = None) -> tuple:
    """Canonical example arguments for AOT lowering, one per spec:
    the exact avals ``prepared.bind_params`` (scalar) and
    ``prepared.stack_params`` (batched, [B]-leading) produce at
    serving time — f32[] for "num", i32[] for "str"/"date" — so an
    ahead-of-time compiled executable accepts every real binding."""
    out = []
    for spec in param_specs:
        dt = np.float32 if spec.typ == "num" else np.int32
        out.append(np.zeros((batch,), dt) if batch is not None
                   else dt(0))
    return tuple(out)


@dataclasses.dataclass
class EvalCtx:
    """Per-trace evaluation context: the active config plus per-stage
    overflow accumulators, one list per OVERFLOW_FLAGS entry.
    Scan-cap overflow (DATASCAN/UNNEST fixed capacity), join-bucket
    overflow (probe width), join-cap overflow (compacted probe-output
    capacity), group-cap overflow (keyed-aggregation segment capacity)
    and topk-cap overflow (the ordered-output sorted tile) are
    surfaced as separate output flags so an adaptive layer can regrow
    exactly the capacity that saturated instead of inflating
    everything."""
    cfg: ExecConfig
    ovf: dict[str, list] = dataclasses.field(
        default_factory=lambda: {f: [] for f in OVERFLOW_FLAGS.values()})
    # profile mode (Executor.compile(profile=True)): per-op traced
    # valid-row counts keyed by the plan's pre-order index, plus the
    # host-side meta dict the trace fills in (obs/profile.py joins it
    # with the static plan). None on normal compiles — the warm path
    # never pays for profiling.
    prof: Optional[dict] = None          # pre-order index -> traced count
    op_index: Optional[dict] = None      # id(op) -> pre-order index
    prof_meta: Optional[dict] = None     # filled at trace time

    def note(self, flag: str, value) -> None:
        """Record one stage's overflow predicate under its registry
        flag (unregistered flags are a programming error — the
        registry is the single source of truth)."""
        self.ovf[flag].append(value)


class Comm:
    """Collective surface, identical under vmap and shard_map.

    Every collective runs under the ``exchange`` named scope, so a
    device profile attributes its operations to the exchange, and
    adds its logical traffic to ``bytes`` at trace time: a b-byte
    operand over P partitions counts b*(P-1)."""

    def __init__(self, axis: Optional[str]):
        self.axis = axis
        self.bytes = 0

    def _count(self, x) -> None:
        x = jnp.asarray(x)
        self.bytes += x.size * x.dtype.itemsize * (self.size() - 1)

    def psum(self, x):
        if not self.axis:
            return x
        self._count(x)
        with jax.named_scope("exchange"):
            return lax.psum(x, self.axis)

    def pmax(self, x):
        if not self.axis:
            return x
        return jnp.max(self.all_gather(x), axis=0)

    def pmin(self, x):
        if not self.axis:
            return x
        return jnp.min(self.all_gather(x), axis=0)

    def all_gather(self, x):
        if not self.axis:
            return x[None] if hasattr(x, "ndim") else jnp.asarray(x)[None]
        self._count(x)
        with jax.named_scope("exchange"):
            return lax.all_gather(x, self.axis)

    def por(self, x):
        return self.psum(x.astype(I32)) > 0

    def index(self):
        return lax.axis_index(self.axis) if self.axis else jnp.int32(0)

    def size(self) -> int:
        if not self.axis:
            return 1
        return lax.axis_size(self.axis)


# ---------------------------------------------------------------------------
# Join machinery
# ---------------------------------------------------------------------------

def _hash_keys(keys: tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """Mix int32 key columns into one int32 hash (verified exactly at
    probe time, so collisions cost a bucket slot, not correctness)."""
    h = jnp.zeros_like(keys[0], dtype=jnp.uint32)
    for k in keys:
        h = (h ^ k.astype(jnp.uint32)) * jnp.uint32(2654435761)
        h = h ^ (h >> 15)
    return h.astype(I32)


def hash_join_probe(build_keys: tuple[jnp.ndarray, ...],
                    build_valid: jnp.ndarray,
                    probe_keys: tuple[jnp.ndarray, ...],
                    probe_valid: jnp.ndarray,
                    bucket: int,
                    use_pallas: bool = False
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Match each probe row to a build row with equal keys.

    Returns (build_pos [T] int32 with -1 for no match, matched [T] bool,
    bucket_overflow bool). Build keys are assumed unique among valid
    rows (M:1 join — the paper's queries; duplicates would surface as
    arbitrary-match, flagged by callers via key-uniqueness checks in
    tests). Sorted-hash + verified bucket probe — the jnp reference
    for kernels/hash_join.py.
    """
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.hash_join_probe(build_keys, build_valid, probe_keys,
                                    probe_valid, bucket=bucket)
    nb = build_keys[0].shape[0]
    hb = _hash_keys(build_keys)
    hb = jnp.where(build_valid, hb, jnp.int32(np.iinfo(np.int32).max))
    order = jnp.argsort(hb)
    hs = hb[order]
    hp = _hash_keys(probe_keys)
    lo = jnp.searchsorted(hs, hp)
    hi = jnp.searchsorted(hs, hp, side="right")
    bucket_overflow = jnp.any((hi - lo) > bucket) & jnp.any(probe_valid)
    pos = jnp.full(probe_keys[0].shape, -1, I32)
    for j in range(bucket):
        cand = jnp.clip(lo + j, 0, nb - 1)
        bidx = order[cand]
        ok = (lo + j) < hi
        for bk, pk in zip(build_keys, probe_keys):
            ok = ok & (bk[bidx] == pk)
        ok = ok & build_valid[bidx] & probe_valid
        pos = jnp.where((pos < 0) & ok, bidx.astype(I32), pos)
    matched = pos >= 0
    return pos, matched, bucket_overflow


# dense-compare segment mapping beats searchsorted's per-row scan up
# to roughly this many dictionary slots (kernels benchmark sweep)
SEG_COMPARE_CAP_MAX = 256


def _sorted_distinct(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Smallest ``k`` distinct values of ``x`` below int32-max,
    ascending, padded with int32-max — exactly
    ``jnp.unique(x, size=k, fill_value=int32max)`` when int32-max
    marks invalid entries, but scatter-free: one sort, then a
    cumsum-rank compaction via searchsorted (XLA CPU lowers
    ``jnp.unique``'s scatter to a serial while-loop; this form stays
    vectorized)."""
    big = jnp.int32(np.iinfo(np.int32).max)
    xs = jnp.sort(x)
    isnew = jnp.concatenate(
        [jnp.ones((1,), bool), xs[1:] != xs[:-1]]) & (xs < big)
    rank = jnp.cumsum(isnew.astype(I32))      # 1-based, steps at news
    idx = jnp.searchsorted(rank, jnp.arange(1, k + 1, dtype=I32))
    vals = jnp.take(xs, jnp.clip(idx, 0, xs.shape[0] - 1))
    return jnp.where(jnp.arange(k) < rank[-1], vals, big)


def _capped_uniques(masked_sid: jnp.ndarray, k: int,
                    comm: Comm) -> jnp.ndarray:
    """Globally-consistent smallest ``k`` distinct sids (invalid rows
    pre-masked to int32-max), big-padded — the capped group
    dictionary. Compacts *per partition first* (the global smallest k
    distinct values are each among some partition's smallest k
    distinct, so the union of per-partition prefixes covers them),
    then all-gathers only [P, k] instead of [P, N] and compacts the
    merged prefix. Bit-identical to ``jnp.unique`` over the full
    gather with ``size=k, fill_value=int32max``."""
    local = _sorted_distinct(masked_sid, k)
    gathered = comm.all_gather(local)
    return _sorted_distinct(gathered.reshape(-1), k)


def _exchange(keys: tuple, valid, cols: dict, comm: Comm,
              dest) -> tuple[tuple, Any, dict]:
    """Partition exchange. ``dest=None``: broadcast (all_gather, the
    hybrid-hash build). Otherwise keep only rows hashed to this
    partition (grace repartition; lowers to all-to-all on real pods —
    built here from all_gather + own-slot select so one implementation
    serves vmap-sim and shard_map)."""
    mine = comm.index()

    def flat(x):
        g = comm.all_gather(x)
        return g.reshape((-1,) + g.shape[2:])

    out_keys = tuple(flat(k) for k in keys)
    v = flat(valid)
    if dest is not None:
        v = v & (flat(dest) == mine)
    out_cols = {}
    for var, c in cols.items():
        if c.kind in ("det", "xnode"):
            out_cols[var] = Col(c.kind, tuple(flat(d) for d in c.data),
                                c.table)
        else:
            out_cols[var] = Col(c.kind, flat(c.data), c.table)
    return out_keys, v, out_cols


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

class PlanError(ValueError):
    pass


class Executor:
    """Compiles logical plans against a Database and runs them."""

    def __init__(self, db: xdm.Database, config: ExecConfig = None):
        self.db = db
        self.config = config or ExecConfig()
        self.tables = device_tables(db)
        parts = {len(c.partitions) for c in db.collections.values()}
        assert len(parts) == 1, "collections must agree on partitioning"
        self.num_partitions = parts.pop()
        # the mesh the tables were last placed on (spmd mode)
        self._placed_on: Optional[tuple] = None
        # observability for the service layer's cache assertions
        self.compile_count = 0      # Executor.compile invocations
        self.trace_count = 0        # actual local-fn traces (retraces)
        # set once a donated run consumes self.tables (they are shared
        # by every compiled variant, so donation spends the executor)
        self._tables_donated = False

    # -- table plumbing ----------------------------------------------------

    def _table_slice_axes(self):
        """in_axes tree: partition axis 0 for collections, None for the
        shared derived arrays."""
        axes = {}
        for k, v in self.tables.items():
            if k == "__derived__":
                axes[k] = jax.tree.map(lambda _: None, v)
            else:
                axes[k] = jax.tree.map(lambda _: 0, v)
        return axes

    def _table_specs(self, axis: str) -> dict:
        """PartitionSpec tree: collections split over ``axis`` (one
        partition per device), the derived arrays replicated."""
        from jax.sharding import PartitionSpec as P
        return {k: jax.tree.map(lambda _: P() if k == "__derived__"
                                else P(axis), v)
                for k, v in self.tables.items()}

    def place_on_mesh(self, mesh, axis: str = "data") -> None:
        """Put the tables on ``mesh`` once — partition p on device p,
        ``__derived__`` replicated — so spmd calls find their inputs
        where shard_map wants them instead of resharding from one
        device on every call. Each device holds exactly one partition,
        so a mesh axis whose size differs from the partition count
        raises ``InvalidArgumentError`` instead of dropping partitions."""
        size = mesh.shape[axis]
        if size != self.num_partitions:
            raise InvalidArgumentError(
                f"mesh axis {axis!r} has {size} devices but the "
                f"database has {self.num_partitions} partitions; "
                f"build it with num_partitions={size}")
        if self._placed_on == (mesh, axis):
            return
        from jax.sharding import NamedSharding, PartitionSpec
        shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), self._table_specs(axis),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        self.tables = jax.device_put(self.tables, shardings)
        self._placed_on = (mesh, axis)

    # -- plan compilation ----------------------------------------------------

    def compile(self, plan: A.Op, mode: str = "sim", mesh=None,
                axis: str = "data", donate: bool = False,
                config: Optional[ExecConfig] = None,
                param_specs: tuple = (),
                batch: Optional[int] = None,
                profile: bool = False,
                aot: bool = False,
                lower_only: bool = False) -> "CompiledPlan":
        """Returns a CompiledPlan whose fn maps tables -> raw arrays
        (stacked over partitions); static column schema is captured at
        trace time (strings can't flow through vmap/shard_map).

        ``config`` overrides the executor's default ExecConfig for this
        compilation only — the service layer uses this to recompile the
        same plan with grown capacities without rebuilding the executor
        (device tables are shared across all compiled variants).
        ``donate=True`` donates the table buffers to the call (one-shot
        runs only; a donated CompiledPlan must not be reused).

        ``param_specs`` enables the prepared-query calling convention:
        the plan may contain ``algebra.Param`` leaves and the compiled
        fn takes ``(tables, params)`` where ``params`` is a tuple of
        traced scalars (one per spec) — a binding change is a new
        argument, never a recompilation. ``batch=B`` additionally maps
        the fn over a leading [B] axis of every param (one device
        dispatch serving B concurrent bindings of the same plan).

        ``profile=True`` additionally outputs a per-operator global
        valid-row count (``prof_rows``, one slot per pre-order plan
        op that executes unfused) — the runtime half of
        ``QueryService.explain(profile=True)``. The extra reduction
        changes the compiled artifact, so profile variants cache
        separately from serving variants and the warm path never
        carries the cost.

        ``aot=True`` lowers and compiles ahead of time against the
        executor's own tables plus canonical example parameters
        (``example_params``), returning a ``jax.stages.Compiled`` in
        ``CompiledPlan.fn`` instead of a lazily-traced jitted
        wrapper. Same call convention and results (``bind_params``
        produces exactly the example argument avals), but the
        executable is concrete — which is what the persistent plan
        cache (core/persist.py) serializes. Ignored for donated
        compilations (one-shot by contract, nothing to persist).
        ``lower_only=True`` stops an ``aot`` compilation after the
        trace and lowering: ``fn`` is the ``jax.stages.Lowered``, and
        the caller makes it the executable with its ``compile()``,
        which reads no state of the executor and so may run on another
        thread (``QueryService.warmup`` runs several at once)."""
        cfg = resolve_kernel_policy(plan, config or self.config)
        self.compile_count += 1
        schema: dict[int, tuple] = {}
        prof_meta: Optional[dict] = {} if profile else None
        op_index = ({id(op): i for i, op in enumerate(A.walk(plan))}
                    if profile else None)
        traced: dict = {}
        jit = partial(jax.jit, donate_argnums=(0,)) if donate else jax.jit
        if batch is not None and not param_specs:
            raise ValueError("batched compilation needs parameters")

        def local(tables, params=()):
            self.trace_count += 1
            ev = ExprEval(self.db, tables, params=params)
            comm = Comm(axis)
            if profile:
                ctx = EvalCtx(cfg, prof={}, op_index=op_index,
                              prof_meta=prof_meta)
            else:
                ctx = EvalCtx(cfg)
            tile = self._eval(plan, ev, comm, None, ctx)
            with jax.named_scope("outputs"):
                out = self._outputs(plan, tile, ev, schema, ctx)
            # one request's collectives; a batched run moves B of them
            traced["exchange_bytes"] = comm.bytes * (batch or 1)
            return out

        if mode == "sim":
            if param_specs:
                # params broadcast to every partition; the optional
                # outer vmap maps the whole partition-parallel program
                # over stacked parameter vectors
                fn = jax.vmap(local,
                              in_axes=(self._table_slice_axes(), None),
                              axis_name=axis)
                if batch is not None:
                    fn = jax.vmap(fn, in_axes=(None, 0))
            else:
                fn = jax.vmap(local, in_axes=(self._table_slice_axes(),),
                              axis_name=axis)
            out_fn = jit(fn)
            if aot and not donate:
                out_fn = self._aot_lower(out_fn, param_specs, batch,
                                         lower_only)
            return CompiledPlan(out_fn, schema, plan, cfg, mode,
                                donated=donate, param_specs=param_specs,
                                batch=batch, profile_meta=prof_meta,
                                traced=traced)
        if mode == "spmd":
            from jax.sharding import PartitionSpec as P

            self.place_on_mesh(mesh, axis)
            table_specs = self._table_specs(axis)

            def local_spmd(tables, params=()):
                # shard_map keeps the (now size-1) partition axis;
                # squeeze it for the local fn, restore on outputs
                der = tables["__derived__"]
                colls = {k: jax.tree.map(lambda a: a[0], v)
                         for k, v in tables.items() if k != "__derived__"}
                colls["__derived__"] = der
                if batch is not None:
                    # batched dispatch under shard_map: the stacked
                    # [B]-leading params arrive replicated on every
                    # device (P() in_spec) and the batch vmap sits
                    # OUTSIDE the mesh axis — collectives inside still
                    # reduce over "data" only, so one dispatch serves
                    # B bindings across all partitions. Outputs get
                    # the partition axis back at position 1, matching
                    # sim mode's [B, P, ...] layout.
                    out = jax.vmap(lambda p: local(colls, p))(params)
                    return jax.tree.map(lambda a: a[:, None], out)
                return jax.tree.map(lambda a: a[None],
                                    local(colls, params))

            if param_specs:
                # params replicated on every device
                in_specs = (table_specs,
                            tuple(P() for _ in param_specs))
            else:
                in_specs = (table_specs,)
            out_spec = P(None, axis) if batch is not None else P(axis)
            sm = jax.shard_map(local_spmd, mesh=mesh, in_specs=in_specs,
                               out_specs=out_spec, check_vma=False)
            out_fn = jit(sm)
            if aot and not donate:
                out_fn = self._aot_lower(out_fn, param_specs, batch,
                                         lower_only)
            return CompiledPlan(out_fn, schema, plan, cfg, mode,
                                donated=donate, param_specs=param_specs,
                                batch=batch, profile_meta=prof_meta,
                                traced=traced)
        raise ValueError(mode)

    def _aot_lower(self, jitted, param_specs: tuple,
                   batch: Optional[int], lower_only: bool):
        """jitted wrapper -> ``jax.stages.Compiled`` via lower+compile
        with the bound tables and canonical example parameters (or the
        ``jax.stages.Lowered`` alone, ``lower_only``). One trace either
        way; AOT just makes the executable a first-class value
        (serializable by core/persist.py) instead of a cache entry
        inside jit."""
        args = (self.tables,)
        if param_specs:
            args += (example_params(param_specs, batch),)
        lowered = jitted.lower(*args)
        return lowered if lower_only else lowered.compile()

    def run(self, plan: A.Op, mode: str = "sim", mesh=None,
            config: Optional[ExecConfig] = None) -> "ResultSet":
        cp = self.compile(plan, mode=mode, mesh=mesh, config=config)
        return self.run_compiled(cp)

    def run_compiled(self, cp: "CompiledPlan",
                     params: Optional[tuple] = None) -> "ResultSet":
        """Execute an already-compiled plan against the bound tables.
        Parameterized plans take their binding via ``params`` (tuple of
        scalars matching ``cp.param_specs``)."""
        if cp.batch is not None:
            raise RuntimeError("batched plans go through "
                               "run_compiled_batch")
        self._check_runnable(cp)
        if cp.param_specs:
            if params is None or len(params) != len(cp.param_specs):
                raise ValueError(
                    f"plan expects {len(cp.param_specs)} parameters, "
                    f"got {None if params is None else len(params)}")
            args = (self.tables, tuple(params))
        else:
            args = (self.tables,)
        tr = obs_trace.current()
        with tr.span("launch", cat="service"):
            out = cp.fn(*args)
        # a trace/compile error above consumed nothing (executor stays
        # usable); once dispatch returned, buffers are donated even if
        # the fetch below fails — flip the flags in between
        if cp.donated:
            cp.spent = True
            self._tables_donated = True
        raw, nbytes = _fetch(out, tr)
        rs = ResultSet(self.db, cp.plan, raw, cp.schema,
                       profile_meta=cp.profile_meta)
        rs.fetch_bytes = nbytes
        return rs

    def run_compiled_batch(self, cp: "CompiledPlan", stacked: tuple,
                           count: int) -> list["ResultSet"]:
        """One batched device dispatch: ``stacked`` holds [B]-leading
        parameter arrays (B = cp.batch); the first ``count`` slices are
        real requests, the rest padding. Returns one ResultSet per real
        request."""
        assert cp.batch is not None and count <= cp.batch
        self._check_runnable(cp)
        tr = obs_trace.current()
        with tr.span("launch", cat="service"):
            out = cp.fn(self.tables, stacked)
        if cp.donated:
            cp.spent = True
            self._tables_donated = True
        raw, nbytes = _fetch(out, tr)

        def take(v, b):
            return tuple(d[b] for d in v) if isinstance(v, tuple) \
                else v[b]

        rss = [ResultSet(self.db, cp.plan,
                         {k: take(v, b) for k, v in raw.items()},
                         cp.schema, profile_meta=cp.profile_meta)
               for b in range(count)]
        for rs in rss:
            rs.fetch_bytes = nbytes     # the batch's one shared copy
        return rss

    def _check_runnable(self, cp: "CompiledPlan") -> None:
        if self._tables_donated:
            raise RuntimeError(
                "this executor's table buffers were donated to an "
                "earlier run; build a new Executor to keep querying")
        if cp.donated and cp.spent:
            raise RuntimeError(
                "donated CompiledPlan already executed once; its "
                "table buffers were donated to that call — "
                "recompile without donate for reuse")

    # -- recursive evaluation -------------------------------------------------

    def _trivial_tile(self) -> Tile:
        return Tile(cols={}, valid=jnp.ones((1,), jnp.bool_),
                    overflow=jnp.zeros((), jnp.bool_))

    def _eval(self, op: A.Op, ev: ExprEval, comm: Comm,
              nts_input: Optional[Tile], ctx: EvalCtx) -> Tile:
        tile = self._eval_op(op, ev, comm, nts_input, ctx)
        if ctx.prof is not None:
            # profile mode: record each op's global valid-row count.
            # Ops that execute fused into a parent (OrderBy under
            # Limit, Aggregate under Subplan) never pass through here
            # and stay absent — obs/profile marks them fused.
            idx = ctx.op_index.get(id(op))
            if idx is not None:
                ctx.prof[idx] = jnp.sum(tile.valid.astype(I32))
        return tile

    def _eval_op(self, op: A.Op, ev: ExprEval, comm: Comm,
                 nts_input: Optional[Tile], ctx: EvalCtx) -> Tile:
        if isinstance(op, A.EmptyTupleSource):
            return self._trivial_tile()
        if isinstance(op, A.NestedTupleSource):
            return nts_input if nts_input is not None \
                else self._trivial_tile()
        if isinstance(op, A.DataScan):
            below = self._eval(op.child, ev, comm, nts_input, ctx)
            if below.cols:
                raise PlanError("DATASCAN over non-trivial input "
                                "(correlated scan not supported)")
            tab = ev.tables.get(op.collection)
            if tab is None:
                known = sorted(k for k in ev.tables if k != "__derived__")
                raise PlanError(f"unknown collection {op.collection!r}; "
                                f"known: {known}")
            mask = path_match_mask(tab, self.db.names, op.path)
            cap = ctx.cfg.scan_cap or tab["kind"].shape[0]
            idx, valid, ovf = rows_from_mask(mask, cap)
            ctx.note("overflow_scan", ovf)
            return Tile(cols={op.var: Col("node", idx, op.collection)},
                        valid=valid, overflow=below.overflow | ovf)
        if isinstance(op, A.Assign):
            t = self._eval(op.child, ev, comm, nts_input, ctx)
            t.cols[op.var] = ev.eval(op.expr, t.cols)
            return t
        if isinstance(op, A.Select):
            t = self._eval(op.child, ev, comm, nts_input, ctx)
            b = ev.eval(op.expr, t.cols)
            return Tile(t.cols, t.valid & b.data, t.overflow)
        if isinstance(op, A.Unnest):
            return self._eval_unnest(op, ev, comm, nts_input, ctx)
        if isinstance(op, A.Subplan):
            outer = self._eval(op.child, ev, comm, nts_input, ctx)
            if not isinstance(op.plan, A.Aggregate):
                raise PlanError("SUBPLAN must have been rewritten to an "
                                "aggregate (run the optimizer first)")
            return self._eval_aggregate(op.plan, ev, comm, outer, ctx)
        if isinstance(op, A.Join):
            return self._eval_join(op, ev, comm, nts_input, ctx)
        if isinstance(op, A.GroupBy):
            return self._eval_group_by(op, ev, comm, nts_input, ctx)
        if isinstance(op, A.OrderBy):
            return self._eval_orderby(op, ev, comm, nts_input, ctx,
                                      limit=None)
        if isinstance(op, A.Limit):
            if isinstance(op.child, A.OrderBy):
                # top-k pushdown: the limit fuses into the sort, so
                # the effective output need is k rows, not every
                # valid group — topk_cap ~ k suffices
                return self._eval_orderby(op.child, ev, comm,
                                          nts_input, ctx, limit=op.k)
            t = self._eval(op.child, ev, comm, nts_input, ctx)
            keep = jnp.cumsum(t.valid.astype(I32)) <= op.k
            return Tile(t.cols, t.valid & keep, t.overflow)
        if isinstance(op, A.DistributeResult):
            return self._eval(op.child, ev, comm, nts_input, ctx)
        raise PlanError(f"cannot execute {type(op).__name__}")

    def _eval_group_by(self, op: "A.GroupBy", ev, comm, nts_input,
                       ctx: EvalCtx) -> Tile:
        t = self._eval(op.child, ev, comm, nts_input, ctx)
        with jax.named_scope("group_by"):
            return self._group_by(op, ev, comm, t, ctx)

    def _group_by(self, op: "A.GroupBy", ev, comm, t: Tile,
                  ctx: EvalCtx) -> Tile:
        """Keyed two-step aggregation (XQuery 3.0 group-by, the
        paper's §6 future work): grouping keys are dictionary-encoded
        strings, so the segment space is the string dictionary; the
        local step is a segmented reduce (the seg_aggregate Pallas
        kernel's job), the global step psums the [S] partials — rule
        4.2.2 generalized from scalar to keyed form.

        ``group_cap`` bounds the segment space: instead of one slot
        per dictionary string, the observed distinct key sids are
        collected into a dense cap-sized segment dictionary (globally
        consistent — built from the all-gathered key column, so every
        partition agrees on the layout and the psum stays aligned).
        A (cap+1)-th distinct key raises ``overflow_group_cap`` so the
        service regrows exactly this capacity; at cap >= dictionary
        size the full-dictionary layout is used, where overflow is
        impossible by construction (the regrowth ceiling).

        Two bit-identical implementations, chosen by the resolved
        ``use_pallas_segments`` knob. The fused path builds the capped
        dictionary scatter-free (``_capped_uniques``), maps sids to
        segments by dense compare (small caps) or searchsorted, and
        runs ONE ``kernels.ops.segmented_aggregate`` pass producing
        count/sum/min/max for every value column together — no
        scatters, no ``jnp.unique``, so XLA CPU never serializes it
        into while-loops, and on TPU it is the Pallas segment kernel.
        Both paths read the same ``group_cap`` and raise the same
        ``overflow_group_cap`` flag: the knob changes implementation,
        never capacity semantics (core.analysis.capflow's contract)."""
        key = ev.eval(op.key_expr, t.cols)
        sid = ev.atom_sid(key)
        dict_size = len(self.db.strings)
        valid = t.valid & (sid >= 0)
        cap = ctx.cfg.group_cap
        fused = bool(ctx.cfg.use_pallas_segments)
        if cap is not None and cap < dict_size:
            # capped segment space: dense dynamic key dictionary
            nseg = cap
            big = jnp.int32(np.iinfo(np.int32).max)
            masked = jnp.where(valid, sid, big)
            if fused:
                uniq = _capped_uniques(masked, cap + 1, comm)
            else:
                gathered = comm.all_gather(masked)
                uniq = jnp.unique(gathered.reshape(-1), size=cap + 1,
                                  fill_value=big)
            govf = uniq[cap] < big      # a (cap+1)-th distinct key
            seg_keys = uniq[:cap]       # sorted ascending, big-padded
            if fused and cap <= SEG_COMPARE_CAP_MAX:
                # == searchsorted-left over the sorted dictionary, as
                # a dense compare (no per-row binary-search scan)
                seg = jnp.sum(sid[:, None] > seg_keys[None, :],
                              axis=1, dtype=I32)
            else:
                seg = jnp.searchsorted(seg_keys, sid).astype(I32)
            seg = jnp.clip(seg, 0, cap - 1)
            valid = valid & (jnp.take(seg_keys, seg) == sid)
            key_col = jnp.where(seg_keys == big, jnp.int32(-1),
                                seg_keys)
        else:
            # full-dictionary segment space: one slot per string sid
            nseg = dict_size
            seg = sid
            govf = jnp.zeros((), jnp.bool_)
            key_col = jnp.arange(nseg, dtype=I32)
        ctx.note("overflow_group_cap", govf)
        cols, g_counts = (
            self._group_aggs_fused(op, ev, t, comm, seg, valid, nseg,
                                   key_col)
            if fused else
            self._group_aggs_legacy(op, ev, t, comm, seg, valid, nseg,
                                    key_col))
        central = comm.index() == 0
        out_valid = (g_counts > 0) & central
        return Tile(cols, out_valid, t.overflow | govf)

    def _group_aggs_fused(self, op, ev, t, comm, seg, valid, nseg,
                          key_col):
        """One fused segmented pass for every aggregate column: stack
        the value columns [N, C], run ``kernels.ops.segmented_aggregate``
        once (count/sum/min/max together), then the usual global step
        (psum for counts/sums, pmin/pmax for extrema). Bit-identical to
        the legacy per-aggregate scatter path: sums accumulate in the
        same row order (one-hot dot_general), min/max are order-exact."""
        from repro.kernels import ops as kops
        specs = []                       # (var, fn, value column idx)
        vcols = []
        for var, fn, val_e in op.aggs:
            if fn == "count":
                specs.append((var, fn, -1))
                continue
            if fn not in ("sum", "avg", "min", "max"):
                raise PlanError(f"group-by aggregate {fn}")
            v = ev.atom_num(ev.eval(val_e, t.cols))
            specs.append((var, fn, len(vcols)))
            vcols.append(v)
        n = seg.shape[0]
        if vcols:
            vals = jnp.stack(vcols, axis=1)
            # NaN-valued rows are excluded from every aggregate value
            # (count still counts them: avg = sum(non-NaN)/count(valid))
            oks = valid[:, None] & ~jnp.isnan(vals)
        else:
            vals = jnp.zeros((n, 0), F32)
            oks = jnp.zeros((n, 0), jnp.bool_)
        counts, sums, mins, maxs = kops.segmented_aggregate(
            vals, oks, seg, valid, nseg)
        g_counts = comm.psum(counts)
        cols: dict[int, Col] = {op.key_var: Col("str", key_col)}
        for var, fn, j in specs:
            if fn == "count":
                cols[var] = Col("num", g_counts)
            elif fn in ("sum", "avg"):
                g = comm.psum(sums[:, j])
                if fn == "avg":
                    g = g / jnp.maximum(g_counts, 1.0)
                cols[var] = Col("num", g)
            elif fn == "min":
                cols[var] = Col("num", comm.pmin(mins[:, j]))
            else:
                cols[var] = Col("num", comm.pmax(maxs[:, j]))
        return cols, g_counts

    def _group_aggs_legacy(self, op, ev, t, comm, seg, valid, nseg,
                           key_col):
        """Per-aggregate scatter-add/scatter-min path — the jnp
        reference the fused path must match bitwise."""
        from repro.kernels import ref as kref

        def seg_sum_count(vals):
            return kref.segmented_sum_count(vals, seg, valid, nseg)

        ones = jnp.ones(seg.shape, F32)
        _, counts = seg_sum_count(ones)
        g_counts = comm.psum(counts)
        cols: dict[int, Col] = {op.key_var: Col("str", key_col)}
        for var, fn, val_e in op.aggs:
            if fn == "count":
                cols[var] = Col("num", g_counts)
                continue
            v = ev.atom_num(ev.eval(val_e, t.cols))
            # NaN-valued rows are excluded from every aggregate value
            # (count still counts them: avg = sum(non-NaN)/count(valid))
            ok = valid & ~jnp.isnan(v)
            if fn in ("sum", "avg"):
                sums, _ = seg_sum_count(jnp.where(ok, v, 0.0))
                g = comm.psum(sums)
                if fn == "avg":
                    g = g / jnp.maximum(g_counts, 1.0)
                cols[var] = Col("num", g)
            elif fn in ("min", "max"):
                safe = jnp.clip(seg, 0, nseg - 1)
                init = jnp.full((nseg,), jnp.inf if fn == "min"
                                else -jnp.inf, F32)
                vv = jnp.where(ok, v, jnp.inf if fn == "min"
                               else -jnp.inf)
                local = (init.at[safe].min(vv) if fn == "min"
                         else init.at[safe].max(vv))
                g = comm.pmin(local) if fn == "min" \
                    else comm.pmax(local)
                cols[var] = Col("num", g)
            else:
                raise PlanError(f"group-by aggregate {fn}")
        return cols, g_counts

    def _eval_orderby(self, op: "A.OrderBy", ev, comm, nts_input,
                      ctx: EvalCtx, limit: Optional[int]) -> Tile:
        t = self._eval(op.child, ev, comm, nts_input, ctx)
        with jax.named_scope("order_by"):
            return self._order_by(op, ev, t, ctx, limit)

    def _order_by(self, op: "A.OrderBy", ev, t: Tile, ctx: EvalCtx,
                  limit: Optional[int]) -> Tile:
        """Capacity-bounded segmented sort over the (grouped) tuple
        stream — ORDER BY, with the top-k pushdown when a LIMIT sits
        directly above. The sorted tile is ``topk_cap`` wide (None:
        the child's full width), so ranked group results never
        materialize the full group dictionary: a limit-k query needs
        only ~k output slots no matter how many segments the reduce
        ran over. Too-small caps raise ``overflow_topk_cap`` (its own
        rung in the service regrowth ladder) — never a silent
        truncation of the ranking."""
        sort_keys: list[tuple] = []
        for e, desc in op.keys:
            col = ev.eval(e, t.cols)
            if col.kind == "str":
                # dictionary sids are insertion-ordered; compare by
                # the derived lexicographic rank so device order ==
                # host string order
                rank = ev.tables["__derived__"]["rank_of_sid"]
                key = _gather(rank, col.data,
                              jnp.int32(np.iinfo(np.int32).max))
            elif col.kind == "date":
                key = col.data
            else:
                key = ev.atom_num(col)
            sort_keys.append((key, desc))
        fused = bool(ctx.cfg.use_pallas_segments) \
            and ctx.cfg.topk_cap is not None
        idx, valid, ovf = topk_rows(sort_keys, t.valid,
                                    ctx.cfg.topk_cap, limit,
                                    fused=fused)
        ctx.note("overflow_topk_cap", ovf)

        def take(c: Col) -> Col:
            if c.kind in ("det", "xnode"):
                return Col(c.kind,
                           tuple(_gather(d, idx,
                                         jnp.nan if d.dtype == F32
                                         else -1)
                                 for d in c.data), c.table)
            if getattr(c.data, "ndim", 1) == 0:
                return c    # row-invariant scalar (const/param)
            if c.data.dtype == jnp.bool_:
                fill = False
            elif c.data.dtype == F32:
                fill = jnp.nan
            else:
                fill = -1
            return Col(c.kind, _gather(c.data, idx, fill), c.table)

        cols = {v: take(c) for v, c in t.cols.items()}
        return Tile(cols, valid, t.overflow | ovf)

    def _eval_unnest(self, op: A.Unnest, ev, comm, nts_input,
                     ctx: EvalCtx) -> Tile:
        t = self._eval(op.child, ev, comm, nts_input, ctx)
        e = op.expr
        if isinstance(e, A.Call) and e.fn == "iterate":
            # singleton iterate == pass-through alias
            t.cols[op.var] = ev.eval(e.args[0], t.cols)
            return t
        if isinstance(e, A.Call) and e.fn == "child":
            return self._unnest_child(t, op.var, e, ev, ctx)
        raise PlanError(f"unnest expr {e}")

    def _unnest_child(self, t: Tile, var: int, e: A.Expr, ev,
                      ctx: EvalCtx) -> Tile:
        """UNNEST child-chain: expand matching descendants, re-gather
        the other columns from each row's ancestor context tuple."""
        from repro.core.rewrite.parallel_rules import _child_chain
        got = _child_chain(e)
        if got is None:
            raise PlanError(f"unsupported unnest chain {e}")
        base_var, names = got
        base = t.cols[base_var]
        assert base.kind == "node"
        tab = ev.tables[base.table]
        n = tab["kind"].shape[0]
        tsize = base.data.shape[0]
        ctx_valid = t.valid & (base.data >= 0)
        safe = jnp.clip(base.data, 0, n - 1)
        in_mask = jnp.zeros((n,), jnp.bool_).at[safe].set(ctx_valid)
        row_of = jnp.full((n,), -1, I32).at[safe].set(
            jnp.where(ctx_valid, jnp.arange(tsize, dtype=I32), -1))
        frontier = in_mask
        name_arr, parent = tab["name"], tab["parent"]
        for nm in names:
            f = self.db.names.lookup(nm)
            up = _gather(frontier, parent, False)
            frontier = up & (name_arr == (f if f >= 0 else -99))
        cap = ctx.cfg.scan_cap or n
        idx, valid, ovf = rows_from_mask(frontier, cap)
        ctx.note("overflow_scan", ovf)
        anc = idx
        for _ in names:
            anc = _gather(parent, anc, -1)
        src = _gather(row_of, anc, -1)
        valid = valid & (src >= 0)

        def regather(c: Col) -> Col:
            if c.kind in ("det", "xnode"):
                return Col(c.kind,
                           tuple(_gather(d, src, -1 if d.dtype != F32
                                         else jnp.nan)
                                 for d in c.data), c.table)
            fill = jnp.nan if c.data.dtype == F32 else -1
            return Col(c.kind, _gather(c.data, src, fill), c.table)

        cols = {v: regather(c) for v, c in t.cols.items()}
        cols[var] = Col("node", idx, base.table)
        return Tile(cols, valid, t.overflow | ovf)

    # -- aggregation -----------------------------------------------------------

    def _eval_aggregate(self, agg: A.Aggregate, ev, comm,
                        outer: Tile, ctx: EvalCtx) -> Tile:
        inner = self._eval(agg.child, ev, comm, outer, ctx)
        expr = agg.expr
        assert isinstance(expr, A.Call)
        fn = expr.fn
        arg = expr.args[0]
        if isinstance(arg, A.Call) and arg.fn == "treat":
            arg = arg.args[0]
        if fn == "count":
            local = jnp.sum(inner.valid.astype(F32))
            total = comm.psum(local)
        else:
            v = ev.atom_num(ev.eval(arg, inner.cols))
            ok = inner.valid & ~jnp.isnan(v)
            if fn == "sum":
                total = comm.psum(jnp.sum(jnp.where(ok, v, 0.0)))
            elif fn == "min":
                local = jnp.min(jnp.where(ok, v, jnp.inf))
                total = comm.pmin(local)
            elif fn == "max":
                local = jnp.max(jnp.where(ok, v, -jnp.inf))
                total = comm.pmax(local)
            elif fn == "avg":
                s = comm.psum(jnp.sum(jnp.where(ok, v, 0.0)))
                c = comm.psum(jnp.sum(ok.astype(F32)))
                total = s / jnp.maximum(c, 1.0)
            else:
                raise PlanError(f"aggregate {fn}")
        col = Col("num", total[None])
        # after the global step every partition holds the total; emit
        # the result tuple only on the "central partition" (§4.2.2)
        central = (comm.index() == 0)[None]
        return Tile(cols={agg.var: col}, valid=central,
                    overflow=inner.overflow | outer.overflow)

    # -- join --------------------------------------------------------------------

    def _eval_join(self, op: A.Join, ev, comm, nts_input,
                   ctx: EvalCtx) -> Tile:
        if not op.hash_keys:
            raise PlanError("non-equi JOIN not supported (no hash keys)")
        cfg = ctx.cfg
        left = self._eval(op.left, ev, comm, nts_input, ctx)
        right = self._eval(op.right, ev, comm, nts_input, ctx)

        def key_arr(col: Col) -> jnp.ndarray:
            # string-dictionary id when present, else packed date,
            # else float bits — all int32, exact
            sid = ev.atom_sid(col)
            date = ev.atom_date(col)
            num = ev.atom_num(col)
            bits = lax.bitcast_convert_type(num, I32)
            return jnp.where(sid >= 0, sid,
                             jnp.where(date >= 0, jnp.int32(1 << 28) + date,
                                       bits))

        lkeys = tuple(key_arr(ev.eval(le, left.cols))
                      for le, _ in op.hash_keys)
        rkeys = tuple(key_arr(ev.eval(re_, right.cols))
                      for _, re_ in op.hash_keys)

        # build-side columns flow upward across the exchange: serialize
        # node refs (Hyracks frame-serialization analogue)
        mine = comm.index()
        lcols = {v: ev.to_xnode(c, mine) for v, c in left.cols.items()}

        if cfg.join_strategy == "broadcast":
            # hybrid-hash analogue: the build side becomes resident on
            # every partition via all_gather; probe stays local
            bkeys, bvalid, bcols = _exchange(
                lkeys, left.valid, lcols, comm, dest=None)
            pkeys, pvalid, pcols = rkeys, right.valid, dict(right.cols)
        elif cfg.join_strategy == "repartition":
            # grace analogue: co-partition BOTH sides by key hash
            p = comm.size()
            ldest = (_hash_keys(lkeys).astype(jnp.uint32)
                     % jnp.uint32(max(p, 1))).astype(I32)
            rdest = (_hash_keys(rkeys).astype(jnp.uint32)
                     % jnp.uint32(max(p, 1))).astype(I32)
            bkeys, bvalid, bcols = _exchange(
                lkeys, left.valid, lcols, comm, dest=ldest)
            rcols = {v: ev.to_xnode(c, mine)
                     for v, c in right.cols.items()}
            pkeys, pvalid, pcols = _exchange(
                rkeys, right.valid, rcols, comm, dest=rdest)
        else:
            raise ValueError(cfg.join_strategy)

        with jax.named_scope("join_probe"):
            pos, matched, bovf = hash_join_probe(
                bkeys, bvalid, pkeys, pvalid, cfg.join_bucket,
                use_pallas=cfg.use_pallas_join)
        ctx.note("overflow_join", bovf)

        def attach(c: Col) -> Col:
            if c.kind in ("det", "xnode"):
                return Col(c.kind,
                           tuple(_gather(d, pos,
                                         jnp.nan if d.dtype == F32 else -1)
                                 for d in c.data), c.table)
            fill = jnp.nan if c.data.dtype == F32 else -1
            return Col(c.kind, _gather(c.data, pos, fill), c.table)

        cols = dict(pcols)
        for v, c in bcols.items():
            cols[v] = attach(c)
        valid = pvalid & matched
        overflow = left.overflow | right.overflow | bovf

        if cfg.join_cap is not None:
            # capacity-bounded probe output: compact matched rows into
            # a fixed-width tile (the Hyracks frame-size analogue for
            # the join's output side). Keeps probe-side blowup bounded
            # and shapes small; overflow surfaces on its own flag so
            # the service regrows join_cap — not the scan cap or the
            # bucket width — when it saturates.
            idx, valid2, jovf = rows_from_mask(valid, cfg.join_cap)
            ctx.note("overflow_join_cap", jovf)

            def compact(c: Col) -> Col:
                if c.kind in ("det", "xnode"):
                    return Col(c.kind,
                               tuple(_gather(d, idx,
                                             jnp.nan if d.dtype == F32
                                             else -1)
                                     for d in c.data), c.table)
                if getattr(c.data, "ndim", 1) == 0:
                    return c    # row-invariant scalar (const/param)
                if c.data.dtype == jnp.bool_:
                    fill = False
                elif c.data.dtype == F32:
                    fill = jnp.nan
                else:
                    fill = -1
                return Col(c.kind, _gather(c.data, idx, fill), c.table)

            cols = {v: compact(c) for v, c in cols.items()}
            valid = valid2
            overflow = overflow | jovf
        return Tile(cols, valid, overflow)

    # -- outputs --------------------------------------------------------------

    def _outputs(self, plan: A.Op, tile: Tile, ev: ExprEval,
                 schema: dict[int, tuple], ctx: EvalCtx) -> dict:
        """Traced arrays only; static (kind, table) goes to ``schema``
        captured at trace time."""
        assert isinstance(plan, A.DistributeResult)

        def or_all(flags):
            acc = jnp.zeros((), jnp.bool_)
            for f in flags:
                acc = acc | f
            return acc

        out: dict[str, Any] = {"valid": tile.valid,
                               "overflow": tile.overflow}
        for flag in OVERFLOW_FLAGS.values():
            out[flag] = or_all(ctx.ovf[flag])
        if ctx.prof is not None:
            # per-op profile counts in pre-order; the static order
            # list reaches the host through the meta dict captured at
            # trace time (same trick as ``schema``)
            order = sorted(ctx.prof)
            out["prof_rows"] = jnp.stack([ctx.prof[i] for i in order])
            ctx.prof_meta["order"] = order
        for v in plan.vars:
            c = tile.cols[v]
            if c.kind == "node":
                schema[v] = ("node", c.table)
                out[f"var{v}"] = c.data
            elif c.kind == "xnode":
                schema[v] = ("xnode", c.table)
                out[f"var{v}"] = c.data       # (part, idx, num, sid, date)
            elif c.kind in ("atom", "det"):
                d = ev.detach(c)
                schema[v] = ("det", None)
                out[f"var{v}"] = d.data       # (num, sid, date) tuple
            else:
                schema[v] = (c.kind, None)
                out[f"var{v}"] = c.data
        return out


# ---------------------------------------------------------------------------
# Result extraction (host)
# ---------------------------------------------------------------------------

def _fetch(out, tr) -> tuple[Any, int]:
    """A run's output tiles copied to the host, and their bytes. An
    enabled tracer splits the copy into ``wait`` (the device finishing
    the program) and ``fetch`` (the transfer itself); otherwise it is
    the one ``device_get``."""
    if tr.enabled:
        with tr.span("wait", cat="service"):
            jax.block_until_ready(out)
        with tr.span("fetch", cat="service"):
            raw = jax.device_get(out)
    else:
        raw = jax.device_get(out)
    return raw, sum(np.asarray(x).nbytes for x in jax.tree.leaves(raw))


@dataclasses.dataclass
class CompiledPlan:
    fn: Callable
    schema: dict[int, tuple]
    plan: A.Op
    config: Optional[ExecConfig] = None   # caps this fn was traced with
    mode: str = "sim"
    donated: bool = False                 # one-shot: tables die with run 1
    spent: bool = dataclasses.field(default=False, repr=False)
    param_specs: tuple = ()               # prepared-query parameter types
    batch: Optional[int] = None           # B of a batched dispatch fn
    profile_meta: Optional[dict] = None   # profile=True: op order,
    #                                       filled at trace time
    traced: dict = dataclasses.field(default_factory=dict)
    #                                       facts filled at trace time

    @property
    def exchange_bytes(self) -> int:
        """Logical bytes the plan's collectives move in one run (0
        until the fn is traced; AOT plans are traced at compile)."""
        return self.traced.get("exchange_bytes", 0)


class ResultSet:
    """Host-side result decoding: rows of python values, plus node
    fingerprints (concatenated descendant text, document order) so
    differential tests can compare against the tree-walking baseline.

    A result keeps the tracer it was produced under (``rows()`` runs
    in its ``decode`` span), the bytes of the device-to-host copy it
    came from (``fetch_bytes``; a batch's results share one copy), and
    ``on_decode``, called with the row count, and the count of those
    rows that hold a node, the first time ``rows()`` decodes (the
    service sets it to count decoded rows)."""

    def __init__(self, db: xdm.Database, plan: A.Op, raw: dict,
                 schema: dict[int, tuple], profile_meta: dict = None):
        self.db = db
        self.plan = plan
        self.raw = raw
        self.schema = schema
        self.profile_meta = profile_meta
        self.tracer = obs_trace.current()
        self.fetch_bytes = 0
        self.on_decode: Optional[Callable[[int, int], None]] = None
        self.overflow = bool(np.any(raw["overflow"]))
        # per-stage flags (absent in pre-refactor raw dicts)
        for flag in OVERFLOW_FLAGS.values():    # overflow_scan, ...
            setattr(self, flag, bool(np.any(raw.get(flag, False))))

    def op_rows(self) -> Optional[dict]:
        """Profile-mode runs only: pre-order plan-op index -> global
        valid rows out of that operator (partition axis summed — per
        the execution model a tile is either partitioned, where the
        sum IS the global count, or valid on the central partition
        only). None on normal runs."""
        if self.profile_meta is None or "prof_rows" not in self.raw:
            return None
        order = self.profile_meta.get("order")
        if order is None:
            return None
        pr = np.asarray(self.raw["prof_rows"])
        per_op = pr.reshape(-1, pr.shape[-1]).sum(axis=0)
        return {idx: int(per_op[j]) for j, idx in enumerate(order)}

    def op_rows_peak(self) -> Optional[dict]:
        """Profile-mode runs only: pre-order plan-op index -> valid
        rows out of that operator on the BUSIEST partition. Capacity
        utilization compares against this (caps are per-partition
        tile sizes); for central-only tiles peak == global count."""
        if self.profile_meta is None or "prof_rows" not in self.raw:
            return None
        order = self.profile_meta.get("order")
        if order is None:
            return None
        pr = np.asarray(self.raw["prof_rows"])
        per_op = pr.reshape(-1, pr.shape[-1]).max(axis=0)
        return {idx: int(per_op[j]) for j, idx in enumerate(order)}

    def rows(self) -> list[tuple]:
        assert isinstance(self.plan, A.DistributeResult)
        with self.tracer.span("decode", cat="service"):
            # the valid slots in row-major order: partition, then slot,
            # the order ordered results (Q11's top-k) arrive in
            part, slot = np.nonzero(np.asarray(self.raw["valid"]))
            cols = [self._column(v, part, slot) for v in self.plan.vars]
            out = list(zip(*cols)) if cols else [()] * len(part)
        if self.on_decode is not None:
            nodes = any(self.schema[v][0] in ("node", "xnode")
                        for v in self.plan.vars)
            self.on_decode(len(out), len(out) if nodes else 0)
            self.on_decode = None           # a result's rows count once
        return out

    def _column(self, v: int, part: np.ndarray, slot: np.ndarray
                ) -> list:
        """Variable ``v`` at the slots ``(part, slot)``, as python
        values: a node as its ``node_fingerprint``, an atom as float,
        int or bool, a string (``None`` for no sid), and a detached
        atom as its string if it has one, else its number."""
        kind, table = self.schema[v]
        data = self.raw[f"var{v}"]

        def at(a):
            return np.asarray(a)[part, slot]

        if kind == "node":
            return node_fingerprints(self.db, table, part, at(data))
        if kind == "xnode":
            return node_fingerprints(self.db, table, at(data[0]),
                                     at(data[1]))
        if kind == "det":
            num = at(data[0]).astype(np.float64).astype(object)
            return self._strings_over(num, at(data[1]))
        if kind == "str":
            return self._strings_over(np.full(len(part), None, object),
                                      at(data))
        if kind == "num":
            return at(data).astype(np.float64).tolist()
        if kind == "date":
            return at(data).astype(np.int64).tolist()
        if kind == "bool":
            return at(data).astype(bool).tolist()
        raise TypeError(kind)

    def _strings_over(self, col: np.ndarray, sid: np.ndarray) -> list:
        """``col`` with the string of each sid >= 0 in its place."""
        has = sid >= 0
        col[has] = self.db.strings.array()[sid[has]]
        return col.tolist()

    def scalar(self) -> float:
        rows = self.rows()
        assert len(rows) == 1 and len(rows[0]) == 1, rows
        return rows[0][0]


def node_fingerprint(db: xdm.Database, collection: str, part: int,
                     idx: int) -> str:
    """Serialize a node as its descendant text values in doc order."""
    t = db.collection(collection).partitions[part]
    if idx < 0 or idx >= t.num_nodes:
        return "<invalid>"
    out = []
    stop = t.num_nodes
    # children are contiguous after the parent in our shred layouts;
    # generic walk: collect all descendants via parent chains
    desc = [idx]
    parents = {idx}
    for j in range(idx + 1, stop):
        par = int(t.parent[j])
        if par in parents:
            parents.add(j)
            desc.append(j)
        elif par < idx:
            break
    for j in desc:
        sid = int(t.text_sid[j])
        if sid >= 0:
            out.append(db.strings.str(sid))
        elif not np.isnan(t.text_num[j]):
            v = float(t.text_num[j])
            out.append(str(int(v)) if v.is_integer() else f"{v:.1f}")
    return "|".join(out)


def node_fingerprints(db: xdm.Database, collection: str, part: np.ndarray,
                      idx: np.ndarray) -> list[str]:
    """``node_fingerprint`` of each node ``(part[i], idx[i])``, by
    column: one pass per source partition over the descendant rows of
    all its nodes (each node's subtree is a contiguous run of rows)."""
    tables = db.collection(collection).partitions
    out = np.empty(len(idx), object)
    for q in np.unique(part).tolist():
        at = np.nonzero(part == q)[0]
        out[at] = _subtree_texts(db.strings, tables[q], idx[at])
    return out.tolist()


def _subtree_texts(strings: xdm.StringDict, t: xdm.NodeTable,
                   idx: np.ndarray) -> np.ndarray:
    out = np.full(len(idx), "<invalid>", object)
    ok = np.nonzero((idx >= 0) & (idx < t.num_nodes))[0]
    start = idx[ok].astype(np.int64)
    length = t.subtree_size[start]
    # the subtree rows of every node, node after node
    first = np.cumsum(length) - length
    rows = np.arange(int(length.sum())) - np.repeat(first - start, length)
    sid, num = t.text_sid[rows], t.text_num[rows]
    keep = (sid >= 0) | ~np.isnan(num)         # rows that give a token
    node = np.repeat(np.arange(len(start)), length)[keep]
    sid, num = sid[keep], num[keep]
    tok = np.empty(len(sid), object)
    is_str = sid >= 0
    tok[is_str] = strings.array()[sid[is_str]]
    # a number's text is formatted once, however many rows hold it
    vals, which = np.unique(num[~is_str], return_inverse=True)
    texts = np.empty(len(vals), object)
    texts[:] = [str(int(v)) if v.is_integer() else f"{v:.1f}"
                for v in vals.tolist()]
    tok[~is_str] = texts[which]
    # join per node, the nodes with c tokens at a time: their tokens,
    # gathered flat, are consumed c by c
    count = np.bincount(node, minlength=len(start))
    begin = np.cumsum(count) - count
    col = np.full(len(start), "", object)
    for c in np.unique(count[count > 0]).tolist():
        at = np.nonzero(count == c)[0]
        flat = iter(tok[(begin[at, None] + np.arange(c)).ravel()].tolist())
        col[at] = list(map("|".join, zip(*[flat] * c)))
    out[ok] = col
    return out
