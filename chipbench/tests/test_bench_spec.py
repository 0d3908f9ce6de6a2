"""BENCHMARK.json keeps its contract, and the loader finds every
configuration, traffic mix, limits file and metric reader by name."""
import json
import os
import re

import pytest

from chipbench import reference, spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_contract_shape():
    assert set(BENCH) == KEYS
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 64 * 1024
    assert all(p == "chipbench" for p in BENCH["paths"])
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [e["name"] for k in KEYS - {"command", "paths", "run_seconds"}
             for e in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for c in BENCH["workloads"]:
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_cell_finds_its_files(cell):
    c = spec.cell(BENCH, cell)
    cfg = spec.config(BENCH, c["config"])
    assert cfg["chips"] == c["chips"]
    assert cfg["name"] == c["config"]
    mix = spec.traffic(c["traffic"])
    for t in mix["templates"]:
        assert t["name"] in reference.KIND
        placeholders = set(re.findall(r"{(\w+)}", t["text"]))
        assert placeholders == {n for p in t["params"] for n in p["set"]}
    lim = spec.limits(cell)
    assert lim["wrong_answers"] == 0 and lim["max_rel_err"] > 0
    per_layer = spec.metrics(BENCH, "per_layer", cell)
    assert per_layer
    e2e = {m["name"] for m in spec.metrics(BENCH, "end_to_end", cell)}
    assert "setup_s" in e2e and len(e2e) > 1
    assert all(m["moves"] in e2e for m in per_layer)


@pytest.mark.parametrize("name", [m["name"] for k in ("end_to_end", "per_layer")
                                  for m in BENCH[k]])
def test_metric_reader_by_name(name):
    assert callable(spec.metric(name).read)


def test_config_entries_match_files():
    for c in BENCH["configs"]:
        path = os.path.join(spec.ROOT, c["file"])
        assert c["file"].startswith("chipbench/configs/")
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) <= set(cfg["source_values"])


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        spec.cell(BENCH, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.traffic("no-such-mix")
