"""BENCHMARK.json resolves, by name, to the files the harness reads."""
from __future__ import annotations

import json
import re

import pytest

from chipbench import run, spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = spec.load_cell(cell, BENCH)
    assert run.driver(c.traffic["kind"])
    assert c.limits
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.reader(m["name"]))
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    # every per-layer metric moves an end-to-end metric this cell reports
    assert {m["moves"] for m in c.per_layer} <= names


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_is_what_the_program_runs(entry):
    config = spec.load_json(spec.REPO / entry["file"])
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    cfg = spec.arch_config(config)             # raises on any difference
    assert cfg.name == config["arch"]


def test_names_and_units():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([m["name"] for m in metrics] + CELLS
             + [c["name"] for c in BENCH["configs"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in metrics:
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert len(json.dumps(BENCH)) < 64 * 1024
