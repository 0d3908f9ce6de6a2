"""The one generator of every traffic mix: a data file in, requests out.

A mix (``traffic/<name>.json``) lists query templates, each with its
text and the draw of every placeholder. Requests come in rounds: each
round holds every template ``weight`` times, in an order drawn from
the seed. Each placeholder draws from a deck: every value of its
domain once, in an order drawn from the seed, then the deck is
shuffled again; a range is cut into ``deck`` equal strata and a deck
holds the midpoint of each. So every seed gets the same sizes of work
in another order, and a window's mix does not swing with the seed.

Domains, read from the configuration:

  stations, states, countries, datatypes, years, dates   the values
      present in the archive (states upper-cased, as Q5 compares)
  month_days     (month, day) of the calendar; sets two placeholders
  int            lo..hi inclusive
  value_range    within the range of the type drawn for ``of``,
                 floor(lo + u (hi - lo)) + 0.5, so no value ties it
  group_sum      the same, times the readings each station has of a
                 type: thresholds on a station's sum

A placeholder marked ``structural`` changes the compiled plan (Q11's
limit); warm-up compiles each of its values.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Iterator

import numpy as np

from chipbench import data


@dataclasses.dataclass(frozen=True)
class Request:
    name: str           # template, as the reference knows it
    args: dict
    text: str


def domains(cfg: dict) -> dict[str, list]:
    facts = data.station_facts(cfg)
    ds = data.dates(cfg)
    return {
        "stations": facts["id"],
        "states": sorted({s.upper() for s in facts["state"]}),
        "countries": [c for c, present in (("FIPS:US", any(facts["is_us"])),
                                           ("FIPS:CA", not all(facts["is_us"])))
                      if present],
        "datatypes": list(cfg["datatypes"]),
        "years": list(range(cfg["years"][0], cfg["years"][1] + 1)),
        "dates": [data.date_str(*d) for d in ds],
        "month_days": [list(md) for md in data.MONTH_DAYS[:cfg["days_per_year"]]],
    }


class _Deck:
    def __init__(self, values: list, rng: np.random.Generator):
        self.values, self.rng, self.left = values, rng, []

    def draw(self):
        if not self.left:
            self.left = [self.values[i]
                         for i in self.rng.permutation(len(self.values))]
        return self.left.pop()


class Generator:
    """Endless request stream of one mix for one configuration and seed."""

    def __init__(self, mix: dict, cfg: dict, seed: int):
        self.mix, self.cfg, self.seed = mix, cfg, seed
        self.dom = domains(cfg)
        self.rng = data.rng(seed, 1)
        strata = mix.get("deck", 16)
        self.units = [(i + 0.5) / strata for i in range(strata)]
        self.order = _Deck([t for t in mix["templates"]
                            for _ in range(t.get("weight", 1))], self.rng)
        self.decks = {(t["name"], i): _Deck(self._values(p), self.rng)
                      for t in mix["templates"]
                      for i, p in enumerate(t.get("params", ()))}

    def _values(self, p: dict) -> list:
        d = p["domain"]
        if d == "int":
            return list(range(p["lo"], p["hi"] + 1))
        if d in ("value_range", "group_sum"):
            return self.units
        return self.dom[d]

    def _value(self, p: dict, drawn, args: dict):
        if p["domain"] in ("value_range", "group_sum"):
            lo, hi = self.cfg["value_ranges"][args[p["of"]]]
            scale = 1
            if p["domain"] == "group_sum":
                scale = len(self.dom["years"]) * self.cfg["days_per_year"]
            return math.floor(scale * (lo + drawn * (hi - lo))) + 0.5
        return drawn

    def request(self, t: dict, args: dict) -> Request:
        return Request(t["name"], args, t["text"].format(**args))

    def _args(self, t: dict) -> dict:
        args: dict = {}
        for i, p in enumerate(t.get("params", ())):
            v = self._value(p, self.decks[t["name"], i].draw(), args)
            names = p["set"]
            args.update(zip(names, v) if len(names) > 1 else {names[0]: v})
        return args

    def __iter__(self) -> Iterator[Request]:
        while True:
            t = self.order.draw()
            yield self.request(t, self._args(t))

    def warm_set(self, sizes, lookahead: int = 64) -> list[Request]:
        """One request per template and value of its structural
        placeholders: the binding with the largest answer among the
        first ``lookahead`` requests of this seed (``sizes(name, args)``
        counts its rows), so capacity regrowth happens here and not in
        the window. Draws from a copy, so the stream stays as it was."""
        g = Generator(self.mix, self.cfg, self.seed)
        best: dict[str, tuple[int, dict]] = {}
        for r in itertools.islice(iter(g), lookahead):
            n = sizes(r.name, r.args)
            if r.name not in best or n > best[r.name][0]:
                best[r.name] = (n, r.args)
        out = []
        for t in self.mix["templates"]:
            args = best[t["name"]][1] if t["name"] in best else g._args(t)
            structural = [p for p in t.get("params", ())
                          if p.get("structural")]
            for combo in itertools.product(
                    *(self._values(p) for p in structural)):
                a = dict(args)
                for p, v in zip(structural, combo):
                    a[p["set"][0]] = v
                out.append(self.request(t, a))
        return out
