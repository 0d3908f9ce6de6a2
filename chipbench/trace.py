"""From a profiler trace to device busy time, kernel time and idle gaps.

``read`` takes the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps two things: the operations of each chip (the ``XLA Ops`` line of
every ``/device:TPU:<n>`` plane) and the benchmark's own host spans
(``TraceAnnotation`` events whose names start with ``bench.``). The
rest of this module works on plain ``(name, start_s, end_s)`` lists,
so the reduction can be checked on events written by hand.

Busy time is the union of a chip's operation intervals inside the
window: an operation that overlaps another counts once.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."

Event = tuple[str, float, float]      # name, start s, end s


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]       # plane name -> device operations
    spans: list[Event]                # host spans of the benchmark
    layout: list[str]                 # "plane / line: events", for a look


def read(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    ops: dict[str, list[Event]] = {}
    spans: list[Event] = []
    layout = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                   for e in line.events]
            layout.append(f"{plane.name} / {line.name}: {len(evs)}")
            if plane.name.startswith(DEVICE_PREFIX) and line.name == OPS_LINE:
                ops.setdefault(plane.name, []).extend(evs)
            spans.extend(e for e in evs if e[0].startswith(SPAN_PREFIX))
    return Trace(ops, sorted(spans, key=lambda e: e[1]), layout)


def union(events) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals covered by the events."""
    out: list[list[float]] = []
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the merged intervals cover."""
    total = 0.0
    for j in range(bisect.bisect_right(merged, lo, key=lambda m: m[1]),
                   len(merged)):
        a, b = merged[j]
        if a >= hi:
            break
        total += min(b, hi) - max(a, lo)
    return total


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that the merged intervals leave idle."""
    out, at = [], lo
    for a, b in merged:
        if b <= lo or a >= hi:
            continue
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


def window(spans) -> tuple[float, float]:
    """First span start to last span end: the traced window."""
    return min(s[1] for s in spans), max(s[2] for s in spans)


def idle_by_span(idle, spans) -> dict[str, float]:
    """Idle seconds attributed to the host span they fall in (the span
    name without its ``bench.`` prefix); idle outside every span reads
    ``between``."""
    spans = sorted(spans, key=lambda e: e[1])
    ends = [e for _, _, e in spans]
    out: collections.Counter = collections.Counter()
    for a, b in idle:
        left = b - a
        for j in range(bisect.bisect_right(ends, a), len(spans)):
            name, s, e = spans[j]
            if s >= b:
                break
            part = max(0.0, min(b, e) - max(a, s))
            out[name[len(SPAN_PREFIX):]] += part
            left -= part
        if left > 1e-12:
            out["between"] += left
    return dict(out)


def op_totals(events) -> dict[str, float]:
    """Summed durations by operation name."""
    out: collections.Counter = collections.Counter()
    for name, a, b in events:
        out[name] += b - a
    return dict(out)


def top(totals: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def label(op: str) -> str:
    """An operation's short name: the HLO instruction's name, and for a
    Pallas kernel its custom-call target."""
    short = op.split(" = ", 1)[0]
    return short + " tpu_custom_call" if "tpu_custom_call" in op else short


def by_request(events, spans) -> dict[str, float]:
    """Seconds by "<template> <operation>", the template read from the
    ``bench.execute:<template>`` span in which the operation started."""
    execs = sorted((s for s in spans if s[0].startswith(SPAN_PREFIX + "execute")),
                   key=lambda s: s[1])
    starts = [s[1] for s in execs]
    out: collections.Counter = collections.Counter()
    for name, a, b in events:
        i = bisect.bisect_right(starts, a) - 1
        where = execs[i][0].split(":", 1)[-1] if i >= 0 and a < execs[i][2] else "-"
        out[f"{where} {label(name)}"] += b - a
    return dict(out)


def summarize(tr: Trace) -> dict:
    """The reduction the metric readers read, over the traced window:

      busy_s        union of each chip's operations, mean over chips
      window_s      first benchmark span start to last span end
      serve_host_s  time inside ``bench.execute`` spans in which no
                    chip ran an operation
      op_s          seconds by operation name, mean over chips
      device_ops    the ten largest operations by template (by_request)
      idle_gaps     seconds no chip was busy, by the host span they
                    fell in, the ten largest
    """
    if not tr.spans or not any(tr.ops.values()):
        raise RuntimeError("the trace holds no benchmark spans or no "
                           "device operations: " + "; ".join(tr.layout))
    lo, hi = window(tr.spans)
    inside = {c: [e for e in evs if e[2] > lo and e[1] < hi]
              for c, evs in tr.ops.items()}
    n = len(inside)
    busy = [covered(union(evs), lo, hi) for evs in inside.values()]
    every = union([e for evs in inside.values() for e in evs])
    host = sum((b - a) - covered(every, a, b) for name, a, b in tr.spans
               if name.startswith(SPAN_PREFIX + "execute"))
    events = [e for evs in inside.values() for e in evs]
    totals = {k: v / n for k, v in op_totals(events).items()}
    per_request = {k: v / n for k, v in by_request(events, tr.spans).items()}
    return {"busy_s": sum(busy) / n, "window_s": hi - lo,
            "serve_host_s": host, "op_s": totals,
            "device_ops": top(per_request),
            "idle_gaps": top(idle_by_span(gaps(every, lo, hi), tr.spans))}
