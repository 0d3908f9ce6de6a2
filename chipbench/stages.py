"""The program's own view of a traced window: its stage spans, the
named scopes of its device program, and its serving counters.

The program mirrors every span of its tracer into the profiler as
``vxq.<span>`` (``execute`` and, inside it, ``prepare``, ``bind``,
``launch``, ``wait``, ``fetch``; then ``decode``), runs its device
operators under named scopes (``rows_from_mask``, ``join_probe``,
``group_by``, ``order_by``, ``outputs``, ``exchange``), names its
three Pallas kernels, and counts ``prepares``, ``fetch_bytes``,
``rows_decoded`` and ``exchange_bytes`` in ``ServiceStats``.

``read`` keeps the program's spans and each device operation's scope
path from a profile; ``summarize`` reduces them over the window that
the benchmark's own spans bound. The readers of the stage metrics in
``metrics/`` read that reduction from ``ctx.program`` and the window's
counters from ``ctx.counters``, and read None where either is missing.

A TPU trace names an operation by its instruction's text, without the
``op_name`` metadata that holds the scope path. ``read`` finds the
path in the text of the executable that ran the operation: each
operation falls in one interval of its chip's ``XLA Modules`` line,
and the executable of that interval is the one, of those of the
module's name, that holds the most of the interval's instructions.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

from chipbench import trace as T

PREFIX = "vxq."
SCOPES = ("rows_from_mask", "join_probe", "group_by", "order_by",
          "outputs", "exchange")
KERNELS = ("hash_join_probe", "seg_aggregate", "seg_topk")
MODULES_LINE = "XLA Modules"
OP_NAME = re.compile(r'op_name="([^"]*)"')
HEAD = re.compile(r"%?([\w.\-]+) = (.+?) ([a-z][\w\-]*)\(")


@dataclasses.dataclass
class Program:
    spans: list[T.Event]                  # the program's spans, unprefixed
    ops: dict[str, list[T.Event]]         # plane -> (scope path, start, end)
    kernels: dict[str, list[T.Event]]     # plane -> (kernel name, start, end)
    sources: dict[str, int]               # where the scope paths came from
    sample: list = dataclasses.field(default_factory=list)
    #                                       a few operations as the trace
    #                                       gives them: name, stats, path


@dataclasses.dataclass
class Executable:
    """What ``read`` needs of one compiled executable's text."""
    name: str                             # its HloModule name
    paths: dict[str, str]                 # instruction name -> op_name
    signatures: frozenset                 # every instruction's signature


def kernel_name(name: str, stats) -> str | None:
    """The Pallas kernel a device operation runs, by the name the
    program gives it; ``unnamed`` for a kernel without one, None for
    an operation that is no kernel."""
    text = " ".join([name, *(str(v) for _, v in stats)])
    for k in KERNELS:
        if k in text:
            return k
    return "unnamed" if "tpu_custom_call" in text else None


def signature(text: str) -> tuple:
    """(name, result shape, opcode, attributes) of one HLO
    instruction's text, which a TPU trace prints with operand shapes
    and without metadata, and ``as_text`` the other way round."""
    text = re.sub(r"/\*index=\d+\*/", "", text)
    m = HEAD.match(text)
    if m is None:
        return (text.lstrip("%").split(" ", 1)[0],)
    depth, at = 1, m.end()
    while at < len(text) and depth:
        depth += {"(": 1, ")": -1}.get(text[at], 0)
        at += 1
    tail = re.split(r", (?:metadata|backend_config)=", text[at:], 1)[0]
    return m.group(1), m.group(2), m.group(3), tail


def executables(texts) -> list[Executable]:
    """Each executable's name, instruction signatures and the
    ``op_name`` of each instruction that carries one, from
    ``as_text()``."""
    out = []
    for text in texts:
        name, paths, sigs = "", {}, set()
        for line in text.splitlines():
            if line.startswith("HloModule "):
                name = line.split()[1].rstrip(",")
            elif " = " in line:
                sig = signature(line.strip().removeprefix("ROOT "))
                sigs.update((sig, sig[:1]))     # a CPU trace: bare names
                m = OP_NAME.search(line)
                if m:
                    paths[sig[0]] = m.group(1)
        out.append(Executable(name, paths, frozenset(sigs)))
    return out


def module_name(event_name: str) -> str:
    """A module's name as its ``XLA Modules`` event gives it, without
    the program id in parentheses: ``jit_local(123)`` -> ``jit_local``."""
    return re.sub(r"\(\d*\)$", "", event_name)


def match(module: str, sigs: set, exes: list[Executable]):
    """The executable, of those named ``module`` (or of all where none
    is), that holds the most of ``sigs``; None where none holds any."""
    cands = [x for x in exes if x.name == module] or exes
    best = max(cands, key=lambda x: len(sigs & x.signatures), default=None)
    return best if best is not None and sigs & best.signatures else None


def op_paths(ops, modules, exes: list[Executable]) -> list[tuple[str, str]]:
    """(scope path, where it came from) of each of one chip's
    operations, given as (name, start) with the chip's module intervals
    (name, start, end): an ``op_name`` in the operation's own text, else
    that of its instruction in the executable of the interval it
    started in. An instruction without metadata, or an operation
    outside every interval, reads ''."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    group: dict[int, list[int]] = collections.defaultdict(list)
    out: list = [None] * len(ops)
    for i, (name, a) in enumerate(ops):
        m = OP_NAME.search(name)
        if m:
            out[i] = (m.group(1), "op_name in the event")
            continue
        j = bisect.bisect_right(starts, a) - 1
        if j >= 0 and a < modules[j][2]:
            group[j].append(i)
        else:
            out[i] = ("", "no module")
    for j, idx in group.items():
        sigs = {i: signature(ops[i][0]) for i in idx}
        exe = match(module_name(modules[j][0]), set(sigs.values()), exes)
        for i in idx:
            path = exe.paths.get(sigs[i][0], "") if exe else ""
            out[i] = (path, "executable text" if path else "none")
    return out


def under(path: str, scope: str) -> bool:
    """Whether a scope path runs under ``scope``: a component of the
    path is the scope, or names it as a transform's argument
    (``vmap(rows_from_mask)``)."""
    return re.search(rf"(^|[/(;]){re.escape(scope)}($|[/);])",
                     path) is not None


def read(log_dir: str, texts=(), device_prefix: str | None = None
         ) -> Program:
    """The program's spans and each device operation's scope path from
    the one profile under ``log_dir``; ``texts`` are the ``as_text()``
    of the executables that may have run."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    exes = executables(texts)
    device_prefix = device_prefix or T.DEVICE_PREFIX
    sample: list = []
    spans: list[T.Event] = []
    ops: dict[str, list[T.Event]] = {}
    kernels: dict[str, list[T.Event]] = {}
    sources: collections.Counter = collections.Counter()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith(device_prefix):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = [(e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in lines.get(MODULES_LINE, ())]
            events = lines.get(T.OPS_LINE, [])
            found = op_paths([(e.name, e.start_ns * 1e-9) for e in events],
                             modules, exes)
            evs = ops.setdefault(plane.name, [])
            kev = kernels.setdefault(plane.name, [])
            for e, (path, src) in zip(events, found):
                sources[src] += 1
                a = e.start_ns * 1e-9
                b = a + e.duration_ns * 1e-9
                evs.append((path, a, b))
                stats = list(e.stats)
                k = kernel_name(e.name, stats)
                if k is not None:
                    kev.append((k, a, b))
                if len(sample) < 6 or (k and len(sample) < 9):
                    sample.append([e.name[:300], [
                        [key, str(v)[:200]] for key, v in stats], path])
            if modules and len(sample) < 10:
                sample.append(["module", modules[0][0][:200], ""])
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    a = e.start_ns * 1e-9
                    spans.append((e.name[len(PREFIX):], a,
                                  a + e.duration_ns * 1e-9))
    return Program(sorted(spans, key=lambda s: s[1]), ops, kernels,
                   dict(sources), sample)


def _clip(events, lo: float, hi: float) -> list[T.Event]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def idle_by_stage(idle, bench, program) -> dict[str, float]:
    """Idle seconds by the benchmark span they fell in and, inside it,
    the innermost program span: ``execute:Q7/fetch``. Idle outside
    every program span keeps the benchmark span's label alone, and
    outside every span reads ``between``, as ``trace.idle_by_span``
    labels them."""
    cuts = sorted({t for _, a, b in (*bench, *program) for t in (a, b)}
                  | {t for a, b in idle for t in (a, b)})
    begin = collections.defaultdict(list)
    for kind, spans in (("bench", bench), ("program", program)):
        for name, a, b in spans:
            if b > a:
                begin[a].append((kind, name, a, b))
    active: list[tuple] = []
    idle = sorted(idle)
    out: collections.Counter = collections.Counter()
    j = 0
    for x, y in zip(cuts, cuts[1:]):
        active = [s for s in active if s[3] > x] + begin.get(x, [])
        while j < len(idle) and idle[j][1] <= x:
            j += 1
        gap = sum(max(0.0, min(b, y) - max(a, x))
                  for a, b in idle[j:bisect.bisect_left(
                      idle, (y, float("inf")))])
        if gap <= 0:
            continue
        outer = [s for s in active if s[0] == "bench"]
        inner = [s for s in active if s[0] == "program"]
        label = outer[0][1][len(T.SPAN_PREFIX):] if outer else "between"
        if inner:
            label += "/" + max(inner, key=lambda s: (s[2], -s[3]))[1]
        out[label] += gap
    return dict(out)


def summarize(prog: Program, bench_spans) -> dict | None:
    """The reduction the stage readers read, over the window the
    benchmark's spans bound; None where the profile holds no program
    span (the program's tracer was off):

      span_s      seconds by program span name (nested spans each
                  count their own length)
      scope_s     device seconds under each named scope: the union of
                  the chip's operations under it, mean over chips
      scoped      whether any operation carried a scope path
      unscoped    share of the operations' device seconds (summed, not
                  merged) that carried no scope path
      kernel_s    device seconds by Pallas kernel name (kernel_name),
                  mean over chips
      idle_gaps   idle seconds by benchmark span and innermost program
                  span, the fifteen largest
      staged_idle share of the idle time inside ``execute`` spans that
                  fell in a program span
    """
    if not prog.spans:
        return None
    lo, hi = T.window(bench_spans)
    spans = _clip(prog.spans, lo, hi)
    span_s: collections.Counter = collections.Counter()
    for name, a, b in spans:
        span_s[name] += b - a
    ops = {c: _clip(evs, lo, hi) for c, evs in prog.ops.items()}
    n = max(1, len(ops))
    scope_s = {s: sum(T.covered(T.union([e for e in evs if under(e[0], s)]),
                                lo, hi) for evs in ops.values()) / n
               for s in SCOPES}
    kernel_s: collections.Counter = collections.Counter()
    for evs in prog.kernels.values():
        for k, a, b in _clip(evs, lo, hi):
            kernel_s[k] += (b - a) / n
    op_s = sum(b - a for evs in ops.values() for _, a, b in evs)
    bare_s = sum(b - a for evs in ops.values() for p, a, b in evs if not p)
    every = T.union([e for evs in ops.values() for e in evs])
    idle = T.gaps(every, lo, hi)
    gaps = idle_by_stage(idle, _clip(bench_spans, lo, hi), spans)
    in_exec = {k: v for k, v in gaps.items() if k.startswith("execute")}
    total = sum(in_exec.values())
    staged = sum(v for k, v in in_exec.items()
                 if "/" in k and not k.endswith("/execute"))
    return {"span_s": dict(span_s), "scope_s": scope_s,
            "scoped": any(p for evs in ops.values() for p, _, _ in evs),
            "unscoped": bare_s / op_s if op_s > 0 else None,
            "kernel_s": dict(kernel_s),
            "idle_gaps": T.top(gaps, 15),
            "staged_idle": staged / total if total > 0 else None}


def span_ms(ctx, name: str):
    """Milliseconds per request in program spans named ``name``."""
    prog = getattr(ctx, "program", None)
    if prog is None:
        return None
    return 1e3 * prog["span_s"].get(name, 0.0) / ctx.window.attempted


def scope_ms(ctx, scope: str):
    """Device milliseconds per request under a named scope."""
    prog = getattr(ctx, "program", None)
    if prog is None or not prog["scoped"]:
        return None
    return 1e3 * prog["scope_s"][scope] / ctx.window.attempted


def counter(ctx, name: str):
    """A serving counter's count over the window, or None."""
    return getattr(getattr(ctx, "counters", None), name, None)


def per_query_mb(ctx, name: str):
    n = counter(ctx, name)
    return None if n is None else n / 1e6 / ctx.window.attempted
