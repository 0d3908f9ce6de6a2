"""Plain numpy answers to the weather queries, straight from the records.

An oracle that shares nothing with the engine: it reads the
generator's record arrays (``weather._make_records``) and the spec's
station facts (state, country, name) and answers each query template
of ``core.workload`` with numpy masks and sorts — no XML, no node
tables, no planner, no executor. ``SaxonLike`` walks the shredded
tree and is exact but slow (Python per node); this one answers a
million readings in well under a second, so it can check the served
path at a deployment's scale.

Results come in the form ``normalize`` gives the engine's rows:

  rows      Q1/Q2/Q5: (date, dataType, station, value) per reading;
            Q6: (station name, date, value)
  scalar    Q3/Q4/Q7/Q8: one float
  grouped   Q9/Q10/Q12: (station, aggregates...) per group
  ordered   Q11: the same, in the query's order
"""
from __future__ import annotations

import collections
import math
from typing import Any

import numpy as np

from repro.core import queries, workload
from repro.data import weather


def _kind(name: str) -> str:
    if name in queries.SCALAR:
        return "scalar"
    if name in queries.ORDERED:
        return "ordered"
    return "grouped" if name in queries.GROUPED else "rows"


KIND = {name: _kind(name) for name in queries.ALL}

# Relative tolerances; every other value compares exactly. Counts,
# sums, min and max of integer tenths are exact in f32 while sums stay
# below 2**24. Averages and the scaled scalars (``div``) round once in
# f32. Q8 sums ~200k differences, past 2**24, so its f32 sum rounds
# as it accumulates.
SCALAR_REL = {"Q3": 1e-6, "Q4": 1e-6, "Q7": 1e-6, "Q8": 1e-5}
AVG_COLUMNS = {"Q9": (2,)}     # grouped columns that are averages
AVG_REL = 1e-6

# the literals of queries.ALL, as arguments of Reference's templates
CANONICAL: dict[str, tuple] = {
    "Q1": ("GHCND:USW00012836", 2003, 12, 25),
    "Q2": ("AWND", 491.744),
    "Q3": ("GHCND:USW00014771", "PRCP", 1999, 10),
    "Q4": ("TMAX", 10),
    "Q5": ("WASHINGTON", "1976-07-04T00:00:00.000"),
    "Q6": ("TMAX", 2000),
    "Q7": ("FIPS:US", "TMIN", 2001, 10),
    "Q8": (10,),
    "Q9": ("TMAX",),
    "Q10": ("PRCP", 100.0),
    "Q11": ("TMAX", 3),
    "Q12": ("PRCP", 2000),
}

# constant variants served through submit/drain beside the canonical
# queries: same-template pairs share one compiled plan and one batch
VARIANTS: tuple[tuple[str, tuple], ...] = (
    ("Q1", ("GHCND:USW00014771", 1999, 7, 4)),
    ("Q1", ("GHCND:USW00012836", 2000, 7, 4)),
    ("Q3", ("GHCND:USW00012836", "TMAX", 2000, 10)),
    ("Q3", ("GHCND:USW00014771", "TMIN", 2001, 10)),
    ("Q9", ("PRCP",)),
    ("Q9", ("SNOW",)),
    ("Q12", ("TMAX", 2001)),
    ("Q12", ("TMIN", 1999)),
)


def query_text(name: str, args: tuple = None) -> str:
    """``queries.ALL[name]``, or its workload template with ``args``."""
    if args is None:
        return queries.ALL[name]
    return getattr(workload, f"{name.lower()}_variant")(*args)


class Reference:
    """Answers for one ``WeatherSpec``; method ``qN`` takes the
    arguments of ``workload.qN_variant``."""

    def __init__(self, spec: weather.WeatherSpec):
        self.spec = spec
        rec = weather._make_records(spec)
        self.station = rec["station"]
        self.dtype = np.asarray(spec.datatypes)[rec["dtype"]]
        self.value = rec["value"].astype(np.float64)
        dates = spec.dates()
        self.date_idx = rec["date"]
        ymd = np.asarray(dates, np.int64)[rec["date"]]
        self.year, self.month, self.day = ymd.T
        self.date_str = [weather._date_str(*d) for d in dates]
        ns = spec.num_stations
        self.station_id = [spec.station_id(i) for i in range(ns)]
        self.index_of = {s: i for i, s in enumerate(self.station_id)}
        self.state = np.asarray([spec.station_state(i)
                                 for i in range(ns)])
        self.is_us = np.asarray([spec.station_is_us(i)
                                 for i in range(ns)])

    # -- helpers ------------------------------------------------------

    def _station_is(self, station: str) -> np.ndarray:
        i = self.index_of.get(station, -1)
        return self.station == i

    def _readings(self, sel: np.ndarray) -> list[tuple]:
        return [(self.date_str[self.date_idx[j]], str(self.dtype[j]),
                 self.station_id[self.station[j]], float(self.value[j]))
                for j in np.nonzero(sel)[0]]

    def _groups(self, sel: np.ndarray) -> dict[str, np.ndarray]:
        """station id -> its selected values, in station order."""
        st, v = self.station[sel], self.value[sel]
        order = np.argsort(st, kind="stable")
        st, v = st[order], v[order]
        keys, starts = np.unique(st, return_index=True)
        return {self.station_id[k]: part
                for k, part in zip(keys, np.split(v, starts[1:]))}

    # -- the query templates (core.workload.qN_variant) -----------------

    def q1(self, station, year, month, day):
        return self._readings(self._station_is(station)
                              & (self.year >= year)
                              & (self.month == month)
                              & (self.day == day))

    def q2(self, datatype, threshold):
        return self._readings((self.dtype == datatype)
                              & (self.value > threshold))

    def q3(self, station, datatype, year, divisor=10):
        sel = (self._station_is(station) & (self.dtype == datatype)
               & (self.year == year))
        return float(self.value[sel].sum()) / divisor

    def q4(self, datatype, divisor=10):
        return float(self.value[self.dtype == datatype].max()) / divisor

    def q5(self, state, datestr):
        y, mo, d = int(datestr[:4]), int(datestr[5:7]), int(datestr[8:10])
        # the ST label's display name is the state, upper-cased by
        # the query
        in_state = np.char.upper(self.state) == state
        return self._readings(in_state[self.station] & (self.year == y)
                              & (self.month == mo) & (self.day == d))

    def q6(self, datatype, year):
        sel = (self.dtype == datatype) & (self.year == year)
        return [(self.spec.station_name(self.station[j]),
                 self.date_str[self.date_idx[j]], float(self.value[j]))
                for j in np.nonzero(sel)[0]]

    def q7(self, country, datatype, year, divisor=10):
        us = self.is_us if country == "FIPS:US" else ~self.is_us
        sel = (us[self.station] & (self.dtype == datatype)
               & (self.year == year))
        return float(self.value[sel].min()) / divisor

    def q8(self, divisor=10):
        nd = len(self.date_str)
        key = self.station.astype(np.int64) * nd + self.date_idx
        lo, hi = self.dtype == "TMIN", self.dtype == "TMAX"
        _, i_lo, i_hi = np.intersect1d(key[lo], key[hi],
                                       assume_unique=True,
                                       return_indices=True)
        diff = self.value[hi][i_hi] - self.value[lo][i_lo]
        return float(diff.mean()) / divisor

    def q9(self, datatype):
        return [(k, float(len(v)), float(v.mean()))
                for k, v in self._groups(self.dtype == datatype).items()]

    def q10(self, datatype, threshold):
        return [(k, float(v.sum()), float(v.max()))
                for k, v in self._groups(self.dtype == datatype).items()
                if v.sum() >= threshold]

    def q11(self, datatype, k=3):
        groups = [(s, float(len(v)), float(v.sum()))
                  for s, v in self._groups(self.dtype == datatype).items()]
        # sum descending, then the grouping key ascending
        groups.sort(key=lambda g: g[0])
        groups.sort(key=lambda g: g[2], reverse=True)
        return groups[:k]

    def q12(self, datatype, year):
        sel = (self.dtype == datatype) & (self.year == year)
        return [(k, float(len(v)), float(v.sum()), float(v.min()),
                 float(v.max()))
                for k, v in self._groups(sel).items()]

    def answer(self, name: str, args: tuple = None) -> Any:
        """The answer to ``queries.ALL[name]`` (``args=None``) or to
        its template with other constants."""
        args = CANONICAL[name] if args is None else args
        return getattr(self, name.lower())(*args)


def normalize(name: str, rows: list[tuple]) -> Any:
    """Engine rows (``ResultSet.rows()`` or ``SaxonLike.run_rows``) in
    the reference's form: node fingerprints split into their fields,
    numbers as floats."""
    kind = KIND[name]
    if kind == "scalar":
        assert len(rows) == 1 and len(rows[0]) == 1, rows
        return float(rows[0][0])
    if kind == "rows":
        if name == "Q6":
            return [(n, d, float(v)) for n, d, v in rows]
        out = []
        for (fp,) in rows:
            date, dtype, station, value = fp.split("|")
            out.append((date, dtype, station, float(value)))
        return out
    return [(r[0],) + tuple(float(x) for x in r[1:]) for r in rows]


def mismatch(name: str, got: Any, want: Any) -> str:
    """'' when ``got`` (normalized) equals ``want`` under the query's
    comparison, else a one-line description of the first difference."""
    kind = KIND[name]
    if kind == "scalar":
        if math.isclose(got, want, rel_tol=SCALAR_REL[name]):
            return ""
        return f"{name}: got {got!r}, want {want!r}"
    if kind == "rows":
        g, w = collections.Counter(got), collections.Counter(want)
        if g == w:
            return ""
        return (f"{name}: {len(got)} rows vs {len(want)}; "
                f"extra {list((g - w).elements())[:3]}, "
                f"missing {list((w - g).elements())[:3]}")
    if kind == "grouped":
        got, want = sorted(got), sorted(want)
    if len(got) != len(want):
        return f"{name}: {len(got)} groups vs {len(want)}"
    avg = AVG_COLUMNS.get(name, ())
    for g, w in zip(got, want):
        ok = g[0] == w[0] and len(g) == len(w) and all(
            math.isclose(a, b, rel_tol=AVG_REL) if c in avg else a == b
            for c, (a, b) in enumerate(zip(g, w)) if c > 0)
        if not ok:
            return f"{name}: got {g!r}, want {w!r}"
    return ""
