"""Logical bytes of the kernel operators of one request.

What a join probe, a grouping or a top-k must at least read and write
to answer the request, counted from the reference's data and not from
the program: rows into and out of each operator, 4 bytes for each key
or value column. The count is the same whatever implements the
operator, and lies below what any implementation moves, so bytes over
the chip's peak bandwidth is a least time. These operators read and
write a few bytes per row and compute next to nothing, so bandwidth
and not arithmetic bounds them.

  join     build rows x keys + probe rows x keys + matched rows
  group    input rows x (key + value) + groups x aggregates returned
  top-k    groups x (order key + grouping key) + k

Q1-Q4 call no kernel operator and count 0.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import Reference

WORD = 4


def _join(build: int, probe: int, matched: int, keys: int = 1) -> int:
    return WORD * (keys * (build + probe) + matched)


def _group(rows: int, groups: int, aggregates: int) -> int:
    return WORD * (2 * rows + groups * aggregates)


def _topk(groups: int, k: int) -> int:
    return WORD * (2 * groups + min(k, groups))


def request_bytes(ref: Reference, name: str, args: dict) -> int:
    n = np.count_nonzero
    nst = len(ref.station_id)
    if name == "Q5":
        y, mo, d = (int(args["datestr"][i:j]) for i, j in ((0, 4), (5, 7), (8, 10)))
        in_state = np.char.upper(ref.state) == args["state"]
        on_date = (ref.year == y) & (ref.month == mo) & (ref.day == d)
        return _join(n(in_state), n(on_date), n(on_date & in_state[ref.station]))
    if name in ("Q6", "Q7"):
        probe = (ref.dtype == args["datatype"]) & (ref.year == args["year"])
        if name == "Q6":
            return _join(nst, n(probe), n(probe))
        us = ref.is_us if args["country"] == "FIPS:US" else ~ref.is_us
        return _join(n(us), n(probe), n(probe & us[ref.station]))
    if name == "Q8":
        lo, hi = n(ref.dtype == "TMIN"), n(ref.dtype == "TMAX")
        return _join(lo, hi, min(lo, hi), keys=2)
    if name in ("Q9", "Q10", "Q11", "Q12"):
        sel = ref.dtype == args["datatype"]
        if name == "Q12":
            sel &= ref.year == args["year"]
        groups = len(np.unique(ref.station[sel]))
        aggregates = {"Q9": 2, "Q10": 2, "Q11": 2, "Q12": 4}[name]
        total = _group(n(sel), groups, aggregates)
        if name == "Q11":
            total += _topk(groups, args["k"])
        return total
    return 0
