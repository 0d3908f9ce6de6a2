"""Finds what ``BENCHMARK.json`` names: cells, configurations, traffic
mixes, limits and metric readers, each in a file of its own.

  configuration   the ``file`` its entry names
  traffic mix     traffic/<traffic>.json
  limits          limits/<cell>.json
  peak rates      peaks.json, by device kind
  metric          metrics/<name>.py, whose ``read(ctx)`` returns the
                  value, or None where the run has nothing to read
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str) -> dict:
    return _json(os.path.join(ROOT, _named(bench["configs"], name,
                                           "configuration")["file"]))


def traffic(name: str) -> dict:
    return _json(os.path.join(HERE, "traffic", f"{name}.json"))


def limits(cell_name: str) -> dict:
    return _json(os.path.join(HERE, "limits", f"{cell_name}.json"))


def peaks() -> dict:
    """Peak rates by ``device_kind``, with their source."""
    return _json(os.path.join(HERE, "peaks.json"))


def metrics(bench: dict, kind: str, cell_name: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", (cell_name,))]


def metric(name: str):
    """The module metrics/<name>.py; its ``read(ctx)`` is the reader."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
