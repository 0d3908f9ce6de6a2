"""Tracing-hazard and determinism linter (pure AST — no jax import).

Static checks for the failure modes that type inference cannot see
because they live in *our* Python, not in the plans:

TRACE001  host cast (``float``/``int``/``bool``) applied to a traced
          value (an argument subtree containing a ``jnp.*``/``lax.*``
          call) inside a traced scope — forces a device sync inside
          jit and breaks under ``shard_map``.
TRACE002  ``.item()`` inside a traced scope — same hazard, spelled as
          a method.
TRACE003  Python ``if``/``while`` whose test contains a ``jnp.*``/
          ``lax.*`` *call* inside a traced scope — control flow on a
          traced value raises ``TracerBoolConversionError`` at best,
          silently specializes at worst.  Attribute comparisons like
          ``x.dtype == jnp.bool_`` are trace-time constants and do
          not fire.
DET001    wall-clock reads (``time.time``/``perf_counter``/
          ``datetime.now``/…) under ``core/`` — results must be a
          function of (plan, data, config), never of the clock.
DET002    unkeyed RNG (legacy ``np.random.<fn>`` global state or the
          stdlib ``random`` module) under ``core/`` — only explicitly
          seeded generators (``np.random.default_rng(seed)``,
          ``jax.random`` keys) keep runs reproducible.
CAP001    an ExecConfig ``*_cap`` field (or ``join_bucket``) missing
          from the executor's ``OVERFLOW_FLAGS`` registry — a
          capacity knob whose overflow nobody can observe.
CAP002    a registry flag never raised via ``ctx.note(flag, ...)`` in
          the executor — an observable that is never written.
CAP003    a registry flag never read as ``rs.overflow_*`` in
          service.py — an overflow with no regrowth rung.
CAP004    a registry cap never presized (no ``dataclasses.replace(...,
          cap=...)`` in service.py) — first-shot configs would always
          start at the fallback ceiling.  ``join_bucket`` is exempt
          (regrowth-only by design: bucket width is a trace-unroll
          factor, not a statistics question).
OBS001    a ``<obj>.stats.<field>`` increment site under ``core/``
          whose field has no entry in ``obs.metrics.
          REGISTERED_STATS`` — a counter the metrics exposition
          silently drops.  Covers ``+=`` and dict-entry writes
          (``stats.d[k] = stats.d.get(k, 0) + 1``).
OBS002    a ``REGISTERED_STATS`` key naming no field of
          ``ServiceStats``/``RuntimeStats`` — a stale registration
          that would export nothing.
KRN001    a Pallas kernel entry point (a top-level function under
          ``kernels/`` whose body builds a ``pl.pallas_call``) with no
          ``kernels/registry.py`` ``KERNEL_REFS`` entry naming an
          existing ``kernels/ref.py`` function — a kernel without a
          declared jnp reference has nothing to hold parity against.
          Stale registry keys (naming no entry point) flag too.

The TRACE rules only apply inside **traced scopes** — the top-level
functions/classes that execute under ``jax.jit``/``shard_map``
(``TRACED_SCOPES`` below, plus everything under ``kernels/``).  Host-
side result materialization legitimately calls ``int()`` on fetched
arrays and must not be flagged.

Waivers: a finding whose line (or the line above it) carries
``# lint: allow(CODE)`` is suppressed — the waiver is the audit trail
for intentional exceptions (e.g. the scheduler's opt-in service-time
measurement).

CLI: ``python -m repro.core.analysis.lint [paths...]`` prints
``path:line:col CODE message`` per finding and exits nonzero if any
survive.  ``scripts/ci.sh --lint`` runs it over ``src/repro``.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
import sys
from typing import Iterable, Optional

# -- configuration -----------------------------------------------------------

#: top-level scopes (per file suffix) whose bodies run under jit /
#: shard_map — the only places the TRACE rules apply.
TRACED_SCOPES = {
    "core/physical.py": ("ExprEval", "path_match_mask",
                         "rows_from_mask", "_compact_search",
                         "_compact_blocked", "topk_rows", "_gather"),
    "core/executor.py": ("Executor", "Comm", "hash_join_probe",
                         "_exchange", "_hash_keys"),
}

#: every file under these directory suffixes is traced end-to-end
TRACED_DIRS = ("kernels/",)

#: DET rules apply only under these directory suffixes
DETERMINISTIC_DIRS = ("core/",)

_HOST_CASTS = ("float", "int", "bool")
_TRACED_MODULES = ("jnp", "lax", "jsp")
_CLOCK_CALLS = ("time", "perf_counter", "monotonic", "now", "utcnow",
                "today")
_SEEDED_RNG_FNS = ("default_rng", "Generator", "SeedSequence",
                   "PCG64", "Philox")

_ALLOW_RE = re.compile(r"#\s*lint:\s*allow\(([A-Z0-9,\s]+)\)")


@dataclasses.dataclass(frozen=True)
class Finding:
    code: str
    path: str
    line: int
    col: int
    message: str

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.code} {self.message}")


# -- helpers -----------------------------------------------------------------


def _norm(path: str) -> str:
    return path.replace(os.sep, "/")


def _in_dirs(path: str, dirs: tuple) -> bool:
    p = _norm(path)
    return any(d in p for d in dirs)


def _traced_names(path: str) -> Optional[tuple]:
    """The traced top-level scope names for this file; () means the
    whole file is traced; None means nothing in it is."""
    p = _norm(path)
    if _in_dirs(p, TRACED_DIRS):
        return ()
    for suffix, names in TRACED_SCOPES.items():
        if p.endswith(suffix):
            return names
    return None


def _attr_chain(e: ast.AST) -> list:
    """``a.b.c`` -> ["a", "b", "c"]; [] when not a pure name chain."""
    parts: list = []
    while isinstance(e, ast.Attribute):
        parts.append(e.attr)
        e = e.value
    if isinstance(e, ast.Name):
        parts.append(e.id)
        return parts[::-1]
    return []


def _has_traced_call(e: ast.AST) -> bool:
    """True when the subtree contains a CALL rooted at a traced-module
    name (``jnp.where(...)``) — calls only, so attribute constants
    like ``jnp.bool_`` in a dtype comparison stay clean."""
    for n in ast.walk(e):
        if isinstance(n, ast.Call):
            chain = _attr_chain(n.func)
            if chain and chain[0] in _TRACED_MODULES:
                return True
    return False


def _waived(lines: list, finding: Finding) -> bool:
    for ln in (finding.line, finding.line - 1):
        if 1 <= ln <= len(lines):
            m = _ALLOW_RE.search(lines[ln - 1])
            if m and finding.code in {c.strip()
                                      for c in m.group(1).split(",")}:
                return True
    return False


# -- the per-file visitor ----------------------------------------------------


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str) -> None:
        self.path = path
        self.findings: list[Finding] = []
        self._traced_names = _traced_names(path)
        self._depth_traced = [self._traced_names == ()]
        self._det = _in_dirs(path, DETERMINISTIC_DIRS)

    def _emit(self, code: str, node: ast.AST, msg: str) -> None:
        self.findings.append(Finding(code, self.path, node.lineno,
                                     node.col_offset, msg))

    @property
    def _traced(self) -> bool:
        return self._depth_traced[-1]

    def _visit_scope(self, node) -> None:
        traced = (self._traced
                  or (self._traced_names is not None
                      and node.name in self._traced_names))
        self._depth_traced.append(traced)
        self.generic_visit(node)
        self._depth_traced.pop()

    visit_FunctionDef = _visit_scope
    visit_AsyncFunctionDef = _visit_scope
    visit_ClassDef = _visit_scope

    # -- TRACE rules -----------------------------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        chain = _attr_chain(node.func)
        if self._traced:
            if (isinstance(node.func, ast.Name)
                    and node.func.id in _HOST_CASTS
                    and any(_has_traced_call(a) for a in node.args)):
                self._emit("TRACE001", node,
                           f"host cast {node.func.id}() on a traced "
                           f"value forces a device sync inside jit")
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                self._emit("TRACE002", node,
                           ".item() on a traced value forces a "
                           "device sync inside jit")
        if self._det and chain:
            self._check_det(node, chain)
        self.generic_visit(node)

    def _check_control(self, node) -> None:
        if self._traced and _has_traced_call(node.test):
            kind = ("if" if isinstance(node, ast.If) else "while")
            self._emit("TRACE003", node,
                       f"Python {kind} on a traced value — use "
                       f"jnp.where / lax.cond / lax.while_loop")
        self.generic_visit(node)

    visit_If = _check_control
    visit_While = _check_control

    # -- DET rules -------------------------------------------------------

    def _check_det(self, node: ast.Call, chain: list) -> None:
        if (len(chain) == 2 and chain[0] in ("time", "datetime")
                and chain[1] in _CLOCK_CALLS):
            self._emit("DET001", node,
                       f"wall-clock read {'.'.join(chain)}() — "
                       f"results must not depend on the clock")
        elif (len(chain) >= 3 and chain[0] in ("np", "numpy")
                and chain[1] == "random"
                and chain[2] not in _SEEDED_RNG_FNS):
            self._emit("DET002", node,
                       f"legacy global-state RNG "
                       f"{'.'.join(chain)}() — use a seeded "
                       f"np.random.default_rng(seed)")
        elif (len(chain) == 2 and chain[0] == "random"
                and chain[1] != "seed"):
            self._emit("DET002", node,
                       f"stdlib random.{chain[1]}() shares hidden "
                       f"global state — use a seeded generator")


# -- entry points ------------------------------------------------------------


def lint_source(text: str, path: str = "<string>") -> list[Finding]:
    """Lint one module's source text (the unit-test API)."""
    try:
        tree = ast.parse(text)
    except SyntaxError as e:
        return [Finding("PARSE", path, e.lineno or 0, e.offset or 0,
                        f"syntax error: {e.msg}")]
    v = _Visitor(path)
    v.visit(tree)
    lines = text.splitlines()
    return [f for f in v.findings if not _waived(lines, f)]


def _py_files(paths: Iterable[str]) -> list:
    out = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for root, _dirs, files in os.walk(p):
            out.extend(os.path.join(root, f) for f in sorted(files)
                       if f.endswith(".py"))
    return sorted(out)


def lint_paths(paths: Iterable[str]) -> list[Finding]:
    findings: list[Finding] = []
    for path in _py_files(paths):
        with open(path, encoding="utf-8") as fh:
            findings.extend(lint_source(fh.read(), path))
    return findings


# -- capacity-registry completeness (cross-file, AST-only) -------------------


def _parse_file(path: str) -> Optional[ast.Module]:
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as fh:
        return ast.parse(fh.read())


def _exec_config_fields(tree: ast.Module) -> list:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "ExecConfig":
            return [s.target.id for s in node.body
                    if isinstance(s, ast.AnnAssign)
                    and isinstance(s.target, ast.Name)]
    return []


def _overflow_registry(tree: ast.Module) -> dict:
    """The literal OVERFLOW_FLAGS dict, read without importing."""
    for node in ast.walk(tree):
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target]
                   if isinstance(node, ast.AnnAssign) else [])
        if (any(isinstance(t, ast.Name) and t.id == "OVERFLOW_FLAGS"
                for t in targets)
                and isinstance(node.value, ast.Dict)):
            out = {}
            for k, v in zip(node.value.keys, node.value.values):
                if (isinstance(k, ast.Constant)
                        and isinstance(v, ast.Constant)):
                    out[k.value] = v.value
            return out
    return {}


def _noted_flags(tree: ast.Module) -> set:
    """Every flag raised via ``<ctx>.note("flag", ...)``."""
    out = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "note" and node.args
                and isinstance(node.args[0], ast.Constant)):
            out.add(node.args[0].value)
    return out


def _read_attrs(tree: ast.Module, prefix: str) -> set:
    return {node.attr for node in ast.walk(tree)
            if isinstance(node, ast.Attribute)
            and node.attr.startswith(prefix)}


def _replace_kwargs(tree: ast.Module) -> set:
    """Every field presized via ``dataclasses.replace(cfg, f=...)``."""
    out = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and _attr_chain(node.func) == ["dataclasses",
                                               "replace"]):
            out.update(kw.arg for kw in node.keywords if kw.arg)
    return out


def lint_registry(repo_src: str) -> list[Finding]:
    """Cross-file capacity-registry completeness over a source tree
    rooted at ``repo_src`` (the directory holding ``repro/``)."""
    exec_path = os.path.join(repo_src, "repro", "core", "executor.py")
    svc_path = os.path.join(repo_src, "repro", "core", "service.py")
    exec_tree = _parse_file(exec_path)
    svc_tree = _parse_file(svc_path)
    if exec_tree is None or svc_tree is None:
        return [Finding("CAP001", repo_src, 0, 0,
                        "cannot locate repro/core/{executor,service}"
                        ".py under this root")]
    findings: list[Finding] = []

    fields = _exec_config_fields(exec_tree)
    registry = _overflow_registry(exec_tree)
    capacity_fields = [f for f in fields
                       if f.endswith("_cap") or f == "join_bucket"]
    for f in capacity_fields:
        if f not in registry:
            findings.append(Finding(
                "CAP001", exec_path, 0, 0,
                f"ExecConfig capacity field {f!r} has no "
                f"OVERFLOW_FLAGS entry — its overflow is "
                f"unobservable"))
    noted = _noted_flags(exec_tree)
    rungs = _read_attrs(svc_tree, "overflow_")
    presized = _replace_kwargs(svc_tree)
    for cap, flag in registry.items():
        if flag not in noted:
            findings.append(Finding(
                "CAP002", exec_path, 0, 0,
                f"registry flag {flag!r} is never raised via "
                f"ctx.note() in the executor"))
        if flag not in rungs:
            findings.append(Finding(
                "CAP003", svc_path, 0, 0,
                f"registry flag {flag!r} is never read in "
                f"service.py — overflow with no regrowth rung"))
        if cap != "join_bucket" and cap not in presized:
            findings.append(Finding(
                "CAP004", svc_path, 0, 0,
                f"registry cap {cap!r} is never presized via "
                f"dataclasses.replace in service.py"))
    return findings


# -- kernel-reference registry completeness (cross-file, AST-only) -----------


def _kernel_refs(tree: ast.Module) -> Optional[dict]:
    """The literal KERNEL_REFS dict (None when the assignment is
    missing — distinct from legitimately empty)."""
    for node in ast.walk(tree):
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target]
                   if isinstance(node, ast.AnnAssign) else [])
        if (any(isinstance(t, ast.Name) and t.id == "KERNEL_REFS"
                for t in targets)
                and isinstance(node.value, ast.Dict)):
            return {k.value: v.value
                    for k, v in zip(node.value.keys, node.value.values)
                    if isinstance(k, ast.Constant)
                    and isinstance(v, ast.Constant)}
    return None


def _pallas_entry_points(tree: ast.Module) -> list:
    """Top-level function names whose body builds a pl.pallas_call."""
    out = []
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for n in ast.walk(node):
                if (isinstance(n, ast.Call)
                        and _attr_chain(n.func) == ["pl",
                                                    "pallas_call"]):
                    out.append(node.name)
                    break
    return out


def lint_kernel_registry(repo_src: str) -> list[Finding]:
    """KRN001 over a source tree rooted at ``repo_src``: every kernel
    entry point declares a jnp reference in kernels/registry.py, every
    declared reference resolves to a kernels/ref.py function, and no
    registry key is stale."""
    kdir = os.path.join(repo_src, "repro", "kernels")
    reg_path = os.path.join(kdir, "registry.py")
    reg_tree = _parse_file(reg_path)
    if reg_tree is None:
        return [Finding("KRN001", repo_src, 0, 0,
                        "cannot locate repro/kernels/registry.py "
                        "under this root")]
    refs = _kernel_refs(reg_tree)
    if refs is None:
        return [Finding("KRN001", reg_path, 0, 0,
                        "no literal KERNEL_REFS dict in "
                        "kernels/registry.py")]
    ref_tree = _parse_file(os.path.join(kdir, "ref.py"))
    ref_fns = ({n.name for n in ref_tree.body
                if isinstance(n, ast.FunctionDef)}
               if ref_tree is not None else set())

    findings: list[Finding] = []
    entry_keys: set = set()
    for path in _py_files([kdir]):
        base = os.path.basename(path)
        if base == "registry.py":
            continue
        tree = _parse_file(path)
        if tree is None:
            continue
        mod = base[:-3]
        for fn in _pallas_entry_points(tree):
            key = f"{mod}.{fn}"
            entry_keys.add(key)
            if key not in refs:
                findings.append(Finding(
                    "KRN001", path, 0, 0,
                    f"kernel entry point {key!r} declares no jnp "
                    f"reference in kernels/registry.py KERNEL_REFS"))
            elif refs[key] not in ref_fns:
                findings.append(Finding(
                    "KRN001", reg_path, 0, 0,
                    f"KERNEL_REFS[{key!r}] names {refs[key]!r}, which "
                    f"is not a function in kernels/ref.py"))
    for key in sorted(set(refs) - entry_keys):
        findings.append(Finding(
            "KRN001", reg_path, 0, 0,
            f"KERNEL_REFS key {key!r} names no pallas_call entry "
            f"point under kernels/ — stale registration"))
    return findings


# -- metrics-registry completeness (cross-file, AST-only) --------------------


def _registered_stats_keys(tree: ast.Module) -> Optional[set]:
    """Keys of the literal REGISTERED_STATS dict (None when the
    assignment is missing — distinct from legitimately empty)."""
    for node in ast.walk(tree):
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target]
                   if isinstance(node, ast.AnnAssign) else [])
        if (any(isinstance(t, ast.Name) and t.id == "REGISTERED_STATS"
                for t in targets)
                and isinstance(node.value, ast.Dict)):
            return {k.value for k in node.value.keys
                    if isinstance(k, ast.Constant)}
    return None


def _class_field_names(tree: ast.Module, cls: str) -> set:
    """Annotated field names of a dataclass body."""
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == cls:
            return {s.target.id for s in node.body
                    if isinstance(s, ast.AnnAssign)
                    and isinstance(s.target, ast.Name)}
    return set()


def _stats_increment_sites(tree: ast.Module) -> list:
    """(node, field) for every write that bumps a stats counter:
    ``<obj>.stats.<field> += n`` and ``<obj>.stats.<field>[k] = ...``
    (the dict-entry form of an increment)."""
    out = []

    def field_of(target: ast.AST) -> Optional[str]:
        if isinstance(target, ast.Subscript):
            target = target.value
        chain = _attr_chain(target)
        if len(chain) >= 3 and chain[-2] == "stats":
            return chain[-1]
        return None

    for node in ast.walk(tree):
        if isinstance(node, ast.AugAssign):
            f = field_of(node.target)
            if f is not None:
                out.append((node, f))
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Subscript):
                    f = field_of(t)
                    if f is not None:
                        out.append((node, f))
    return out


def lint_stats_sources(files: Iterable[tuple],
                       registered: set) -> list[Finding]:
    """OBS001 over (path, source) pairs: every stats increment site
    must name a REGISTERED_STATS key. Waivers honored."""
    findings: list[Finding] = []
    for path, text in files:
        try:
            tree = ast.parse(text)
        except SyntaxError:
            continue
        lines = text.splitlines()
        for node, field in _stats_increment_sites(tree):
            if field in registered:
                continue
            f = Finding(
                "OBS001", path, node.lineno, node.col_offset,
                f"stats field {field!r} is incremented here but has "
                f"no obs.metrics.REGISTERED_STATS entry — it would "
                f"be invisible to the metrics exposition")
            if not _waived(lines, f):
                findings.append(f)
    return findings


def lint_metrics(repo_src: str) -> list[Finding]:
    """Cross-file metrics-registry completeness over a source tree
    rooted at ``repo_src``: OBS001 (unregistered increment sites under
    core/) and OBS002 (stale registrations)."""
    metrics_path = os.path.join(repo_src, "repro", "core", "obs",
                                "metrics.py")
    metrics_tree = _parse_file(metrics_path)
    if metrics_tree is None:
        return [Finding("OBS001", repo_src, 0, 0,
                        "cannot locate repro/core/obs/metrics.py "
                        "under this root")]
    registered = _registered_stats_keys(metrics_tree)
    if registered is None:
        return [Finding("OBS001", metrics_path, 0, 0,
                        "no literal REGISTERED_STATS dict in "
                        "obs/metrics.py")]

    core = os.path.join(repo_src, "repro", "core")
    files = []
    for path in _py_files([core]):
        with open(path, encoding="utf-8") as fh:
            files.append((path, fh.read()))
    findings = lint_stats_sources(files, registered)

    svc_tree = _parse_file(os.path.join(repo_src, "repro", "core",
                                        "service.py"))
    rt_tree = _parse_file(os.path.join(repo_src, "repro", "core",
                                       "serving", "scheduler.py"))
    fields: set = set()
    if svc_tree is not None:
        fields |= _class_field_names(svc_tree, "ServiceStats")
    if rt_tree is not None:
        fields |= _class_field_names(rt_tree, "RuntimeStats")
    if fields:
        for key in sorted(registered - fields):
            findings.append(Finding(
                "OBS002", metrics_path, 0, 0,
                f"REGISTERED_STATS key {key!r} names no field of "
                f"ServiceStats/RuntimeStats — stale registration"))
    return findings


# -- CLI ---------------------------------------------------------------------


def main(argv: Optional[list] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if not args:
        args = ["src/repro"]
    findings = lint_paths(args)
    # registry completeness runs when any arg contains repro/core (or
    # is a tree that does)
    for a in args:
        root = a
        # accept either .../src or .../src/repro
        if _norm(root).rstrip("/").endswith("repro"):
            root = os.path.dirname(root.rstrip("/" + os.sep))
        if os.path.isdir(os.path.join(root, "repro", "core")):
            findings.extend(lint_registry(root))
            findings.extend(lint_metrics(root))
            findings.extend(lint_kernel_registry(root))
            break
    for f in findings:
        print(f)
    if findings:
        print(f"{len(findings)} lint finding(s)", file=sys.stderr)
        return 1
    print(f"lint clean over {', '.join(args)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
