"""Plain numpy answers to the GHCN-Daily templates, and the comparison
that decides ``correct``.

The reference reads the records of ``data.records`` and the station
facts of ``data.station_facts`` and answers each template with masks
and sorts: no XML, no node tables, no planner, no executor, nothing
imported from the program. It computes in float64 (``precision``);
the control computes the same answers in bfloat16, the precision below
the configuration's float32.

Answers have the form ``normalize`` gives the engine's rows:

  rows      Q1/Q2/Q5: (date, dataType, station, value) per reading;
            Q6: (station name, date, value)
  scalar    Q3/Q4/Q7/Q8: one float
  grouped   Q9/Q10/Q12: (station, aggregates...) per group, any order
  ordered   Q11: the same, in the query's order

``compare`` splits each answer into what must be equal exactly (rows,
group keys, counts, sums, minima and maxima of integer tenths, which
float32 holds exactly) and what is rounded once or accumulated in
float32 (scalars divided by 10, averages, Q8's sum of 200k
differences), whose widest relative gap it reports.
"""
from __future__ import annotations

import collections
from typing import Any

import ml_dtypes
import numpy as np

from chipbench import data

KIND = {"Q1": "rows", "Q2": "rows", "Q3": "scalar", "Q4": "scalar",
        "Q5": "rows", "Q6": "rows", "Q7": "scalar", "Q8": "scalar",
        "Q9": "grouped", "Q10": "grouped", "Q11": "ordered",
        "Q12": "grouped"}
AVG_COLUMNS = {"Q9": (2,)}     # grouped columns that are averages
PRECISIONS = {"float64": np.float64, "bfloat16": ml_dtypes.bfloat16}


class Reference:
    """Answers for one configuration's records; ``answer(name, args)``
    takes the template's placeholders by name."""

    def __init__(self, cfg: dict, rec: dict[str, np.ndarray],
                 precision: str = "float64"):
        self.dt = PRECISIONS[precision]
        facts = data.station_facts(cfg)
        ds = data.dates(cfg)
        self.station = rec["station"]
        self.dtype = np.asarray(cfg["datatypes"])[rec["dtype"]]
        self.value = rec["value"].astype(self.dt)
        self.date_idx = rec["date"]
        self.year, self.month, self.day = np.asarray(ds, np.int64)[rec["date"]].T
        self.date_str = [data.date_str(*d) for d in ds]
        self.station_id = facts["id"]
        self.index_of = {s: i for i, s in enumerate(self.station_id)}
        self.name = facts["name"]
        self.state = np.asarray(facts["state"])
        self.is_us = np.asarray(facts["is_us"])

    # -- helpers ------------------------------------------------------

    def _station_is(self, station):
        return self.station == self.index_of.get(station, -1)

    def _sum(self, v):
        return np.add.reduce(v.astype(self.dt), dtype=self.dt)

    def _readings(self, sel):
        idx = np.nonzero(sel)[0]
        return list(zip([self.date_str[d] for d in self.date_idx[idx]],
                        self.dtype[idx].tolist(),
                        [self.station_id[s] for s in self.station[idx]],
                        self.value[idx].astype(np.float64).tolist()))

    def _named_readings(self, sel):
        idx = np.nonzero(sel)[0]
        return list(zip([self.name[s] for s in self.station[idx]],
                        [self.date_str[d] for d in self.date_idx[idx]],
                        self.value[idx].astype(np.float64).tolist()))

    def _groups(self, sel):
        """station id -> its selected values, in station order."""
        st, v = self.station[sel], self.value[sel]
        order = np.argsort(st, kind="stable")
        st, v = st[order], v[order]
        keys, starts = np.unique(st, return_index=True)
        return {self.station_id[k]: part
                for k, part in zip(keys, np.split(v, starts[1:]))}

    def _ten(self, x):
        return float(self.dt(x) / self.dt(10))

    # -- the templates ------------------------------------------------

    def q1(self, station, year, month, day):
        return self._readings(self._station_is(station) & (self.year >= year)
                              & (self.month == month) & (self.day == day))

    def q2(self, datatype, threshold):
        return self._readings((self.dtype == datatype)
                              & (self.value > self.dt(threshold)))

    def q3(self, station, datatype, year):
        sel = (self._station_is(station) & (self.dtype == datatype)
               & (self.year == year))
        return self._ten(self._sum(self.value[sel]))

    def q4(self, datatype):
        return self._ten(self.value[self.dtype == datatype].max())

    def q5(self, state, datestr):
        y, mo, d = int(datestr[:4]), int(datestr[5:7]), int(datestr[8:10])
        in_state = np.char.upper(self.state) == state
        return self._readings(in_state[self.station] & (self.year == y)
                              & (self.month == mo) & (self.day == d))

    def q6(self, datatype, year):
        return self._named_readings((self.dtype == datatype)
                                    & (self.year == year))

    def q7(self, country, datatype, year):
        us = self.is_us if country == "FIPS:US" else ~self.is_us
        sel = us[self.station] & (self.dtype == datatype) & (self.year == year)
        return self._ten(self.value[sel].min())

    def q8(self):
        nd = len(self.date_str)
        key = self.station.astype(np.int64) * nd + self.date_idx
        lo, hi = self.dtype == "TMIN", self.dtype == "TMAX"
        _, i_lo, i_hi = np.intersect1d(key[lo], key[hi], assume_unique=True,
                                       return_indices=True)
        diff = self.value[hi][i_hi] - self.value[lo][i_lo]
        return self._ten(self._sum(diff) / self.dt(len(diff)))

    def q9(self, datatype):
        return [(k, float(len(v)), float(self._sum(v) / self.dt(len(v))))
                for k, v in self._groups(self.dtype == datatype).items()]

    def q10(self, datatype, threshold):
        out = []
        for k, v in self._groups(self.dtype == datatype).items():
            s = self._sum(v)
            if s >= self.dt(threshold):
                out.append((k, float(s), float(v.max())))
        return out

    def q11(self, datatype, k):
        groups = [(s, float(len(v)), float(self._sum(v)))
                  for s, v in self._groups(self.dtype == datatype).items()]
        groups.sort(key=lambda g: g[0])     # ties: grouping key ascending
        groups.sort(key=lambda g: g[2], reverse=True)
        return groups[:k]

    def q12(self, datatype, year):
        sel = (self.dtype == datatype) & (self.year == year)
        return [(k, float(len(v)), float(self._sum(v)), float(v.min()),
                 float(v.max())) for k, v in self._groups(sel).items()]

    def answer(self, name: str, args: dict) -> Any:
        return getattr(self, name.lower())(**args)


def normalize(name: str, rows: list[tuple]) -> Any:
    """Engine rows (``ResultSet.rows()``) in the reference's form: node
    fingerprints split into their fields, numbers as floats."""
    kind = KIND[name]
    if kind == "scalar":
        if len(rows) != 1 or len(rows[0]) != 1:
            raise ValueError(f"{name}: expected one value, got {rows[:3]!r}")
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


def _rel(got: float, want: float) -> float:
    if got == want:
        return 0.0
    return abs(got - want) / max(abs(want), 1e-30)


def compare(name: str, got: Any, want: Any) -> tuple[bool, float]:
    """(exact parts equal, widest relative gap of the rounded parts)."""
    kind = KIND[name]
    if kind == "scalar":
        return True, _rel(got, want)
    if kind == "rows":
        return collections.Counter(got) == collections.Counter(want), 0.0
    if kind == "grouped":
        got, want = sorted(got), sorted(want)
    if len(got) != len(want):
        return False, 0.0
    avg = AVG_COLUMNS.get(name, ())
    exact, rel = True, 0.0
    for g, w in zip(got, want):
        if g[0] != w[0] or len(g) != len(w):
            return False, rel
        for c in range(1, len(w)):
            if c in avg:
                rel = max(rel, _rel(g[c], w[c]))
            elif g[c] != w[c]:
                exact = False
    return exact, rel


class Sizes(Reference):
    """Rows each answer holds (1 for a scalar), without building them:
    what warm-up ranks bindings by."""

    def _readings(self, sel):
        return range(int(np.count_nonzero(sel)))

    _named_readings = _readings

    def size(self, name: str, args: dict) -> int:
        return 1 if KIND[name] == "scalar" else len(self.answer(name, args))
