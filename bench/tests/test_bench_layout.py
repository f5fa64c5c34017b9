"""BENCHMARK.json and the files it names: every cell, configuration, mix and
metric has its file or reader, found by name, and the configuration files
hold the program's widths."""

import json
import os
import re

import pytest

import harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_found_by_name(w):
    cell = harness.find_cell(w["name"])
    assert cell.entry["config"] in {c["name"] for c in BENCH["configs"]}
    assert {"slots", "max_len", "check"} <= set(cell.spec)
    assert ("clients" in cell.spec) == (cell.mix["loop"] == "closed")
    assert ("rate" in cell.spec) == (cell.mix["loop"] == "open")
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    e2e = [m["name"] for m in harness.metrics_for(cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_for(cell, True)


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_match_the_program(c):
    from repro.launch import server as launcher

    assert c["file"] == f"bench/configs/{c['name']}.json"
    data = json.load(open(os.path.join(ROOT, c["file"])))
    assert data["reduced"] == c["reduced"] and data["name"] == c["name"]
    cell = next(harness.find_cell(w["name"]) for w in BENCH["workloads"]
                if w["config"] == c["name"])
    harness.check_config(launcher.build_config(
        harness.server_args(cell, 0, False)), data)


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_found_by_name(m):
    assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    assert callable(harness.reader(m["name"]))
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    else:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        names = {w["name"] for w in BENCH["workloads"]}
        assert set(m["workloads"]) <= names
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_layers_named_alike():
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].split(" ")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
