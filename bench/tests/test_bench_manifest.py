"""BENCHMARK.json against the benchmark's contract, and every name in it
against a file of the benchmark."""
import json
import re

import pytest

from bench_fixtures import ROOT

from bench import harness

M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"] == ["python3", "bench/run.py"]
    assert M["paths"] == ["bench"]
    assert 1 <= M["run_seconds"] <= 51
    # a full check of 24 cells must fit the time it is given
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_entry_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert (ROOT / c["file"]).exists() and c["file"].startswith("bench/")
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for e in M["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for p in M["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert p["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in M["end_to_end"] + M["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [x["name"] for x in M["end_to_end"] + M["per_layer"]]
    assert len(names) == len(set(names)) and len(CELLS) == len(set(CELLS))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files(cell):
    c = harness.load_cell(cell)
    assert c.kind() and c.reference()
    assert c.limits, f"{cell} has no correctness limits"
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert hasattr(harness.metric_reader(m["name"]), "read")


def test_per_layer_moves_a_metric_each_of_its_cells_reports():
    for p in M["per_layer"]:
        moved = [e for e in M["end_to_end"] if e["name"] == p["moves"]]
        assert len(moved) == 1, p["name"]
        for cell in p.get("workloads", CELLS):
            assert cell in CELLS
            assert "workloads" not in moved[0] or cell in moved[0]["workloads"]


def test_layers_named_alike():
    layers = {p["layer"] for p in M["per_layer"]}
    assert all("\n" not in x and 0 < len(x) <= 200 for x in layers)
