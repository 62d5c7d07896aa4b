"""Finds a cell's files by the names that BENCHMARK.json gives.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
Each has a data file of its own:

    chipbench/configs/<config>.json   sizes as run, with source and cuts;
                                      ``bench_arch`` names its family
    chipbench/archs/<bench_arch>.py   what the benchmark knows of a
                                      family's shape: weight layout, the
                                      float32 reference forward, counts
    chipbench/traffic/<traffic>.json  parameters read by traffic_gen.py and
                                      by the driver its ``kind`` names
    chipbench/drive_<kind>.py         the driver of a traffic kind
    chipbench/cells/<cell>.json       the limits that decide ``correct``
    chipbench/metrics/<metric>.py     one reader per metric: read(run)

so a later change adds a cell, a metric or an architecture by adding
files and entries.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
BENCHMARK = REPO / "BENCHMARK.json"


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    return load_json(BENCHMARK)


def metrics_of(bench: Dict[str, Any], cell: str, group: str):
    """The metrics of ``group`` that ``cell`` reports: those whose
    ``workloads`` name it, and those with no ``workloads`` key."""
    return [m for m in bench[group]
            if cell in m.get("workloads", [cell])]


def load_cell(name: str, bench: Dict[str, Any] | None = None) -> Cell:
    bench = bench or load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in {BENCHMARK.name}; "
                       f"have {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(REPO / cfg_entry["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    limits = load_json(HERE / "cells" / f"{name}.json")["limits"]
    return Cell(name, w["chips"], config, traffic, limits,
                metrics_of(bench, name, "end_to_end"),
                metrics_of(bench, name, "per_layer"))


def _load(kind: str, name: str, path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable:
    """``read(run)`` from chipbench/metrics/<metric>.py."""
    return _load("metric", metric, HERE / "metrics" / f"{metric}.py").read


@functools.cache
def _arch_module(name: str):
    path = HERE / "archs" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no architecture module {path.name} in "
                       f"{path.parent}; have "
                       f"{sorted(p.stem for p in path.parent.glob('*.py'))}")
    return _load("arch", name, path)


def arch(config: Dict[str, Any]):
    """The module chipbench/archs/<bench_arch>.py that a configuration
    names: its weight layout, reference forward and counts."""
    return _arch_module(config["bench_arch"])


# published config.json keys common to every family -> the program's
# ArchConfig fields; each arch module adds its own in ARCH_FIELDS
ARCH_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def arch_config(config: Dict[str, Any], exact: bool = True):
    """The program's ArchConfig for a configuration file.

    With ``exact``, every size in the file must equal the program's own
    config for that arch, and so must the keys under ``program`` (how the
    program runs it): the file holds the configuration as it is run.
    Tests pass ``exact=False`` to run a reduced copy.
    """
    from repro.configs import get_config
    base = get_config(config["arch"])
    keys = {**ARCH_FIELDS, **arch(config).ARCH_FIELDS}
    fields = {f: config[k] for k, f in keys.items() if k in config}
    fields["param_dtype"] = config["param_dtype"]
    fields.update(config.get("program", {}))
    if not exact:
        return dataclasses.replace(base, block_pattern=(), **fields)
    wrong = {f: (v, getattr(base, f)) for f, v in fields.items()
             if getattr(base, f) != v}
    if wrong:
        raise ValueError(f"{config['arch']}: file (left) and program "
                         f"(right) differ: {wrong}")
    return base
