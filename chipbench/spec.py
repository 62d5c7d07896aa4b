"""Finds a cell's files by the names that BENCHMARK.json gives.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
Each has a data file of its own:

    chipbench/configs/<config>.json   sizes as run, with source and cuts
    chipbench/traffic/<traffic>.json  parameters read by traffic_gen.py and
                                      by the driver its ``kind`` names
    chipbench/cells/<cell>.json       the limits that decide ``correct``
    chipbench/metrics/<metric>.py     one reader per metric: read(run)

so a later change adds a cell or a metric by adding files and entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
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


def reader(metric: str) -> Callable:
    """``read(run)`` from chipbench/metrics/<metric>.py."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# published config.json keys -> the program's ArchConfig fields
ARCH_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
    "qk_norm": "qk_norm",
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
    fields = {f: config[k] for k, f in ARCH_FIELDS.items() if k in config}
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
