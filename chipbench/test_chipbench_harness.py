"""The harness without a chip: it refuses to run, it needs the program,
and a run at a tiny size prints only the contract's keys."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from chipbench import spec
from chipbench import run as bench_run

ARGS = ["--workload", "qwen3-0.6b.train", "--seed", "1", "--seconds", "1"]
TINY = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 256}


# cell -> (configuration, traffic): the cells the tests drive, named as
# their files are, whether or not BENCHMARK.json lists them yet
CELL_FILES = {"qwen3-0.6b.train": ("qwen3-0.6b", "train_b2_t2048"),
              "qwen3-0.6b.chat": ("qwen3-0.6b", "chat_poisson")}


def tiny_cell(name, **traffic):
    """A cell built from its files, at a size the CPU runs in seconds."""
    config, traffic_name = CELL_FILES[name]
    config = spec.load_json(spec.HERE / "configs" / f"{config}.json")
    cell = spec.Cell(
        name, 1, config,
        spec.load_json(spec.HERE / "traffic" / f"{traffic_name}.json"),
        spec.load_json(spec.HERE / "cells" / f"{name}.json")["limits"],
        [], [])
    cell.config = {**cell.config, **TINY,
                   "program": {**cell.config["program"], "remat": "none"}}
    cell.traffic = {**cell.traffic, **traffic}
    return cell


SERVE_TINY = dict(max_seq=128, slots=4, check_tokens=96,
                  prompt={"lengths": 4, "median": 16, "sigma": 0.7,
                          "min": 8, "max": 48},
                  output={"median": 8, "sigma": 0.5, "min": 4, "max": 32})


def run_tiny(cell, seconds=0.5, seed=2**31 + 17):
    return bench_run.run_cell(cell, seed, seconds, False,
                              devices=jax.devices()[:1], peaks=None,
                              t_start=time.monotonic(), exact=False)


def _cli(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _cli(spec.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(spec.BENCHMARK, tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_result_line_has_only_the_contracts_keys():
    listed = spec.load_cell("qwen3-0.6b.train")
    cell = tiny_cell("qwen3-0.6b.train", seq_len=64)
    cell.end_to_end = listed.end_to_end
    out = run_tiny(cell)
    line = json.loads(json.dumps(out))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == set(spec.load_cell("qwen3-0.6b.train")
                                      .limits)
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_serve_run_is_correct_and_compiles_nothing_in_the_window():
    cell = tiny_cell("qwen3-0.6b.chat", rate_per_s=20.0, **SERVE_TINY)
    cell.per_layer = [{"name": "chat.window_compiles", "unit": "programs"}]
    out = bench_run.run_cell(cell, 5, 1.0, True, devices=jax.devices()[:1],
                             peaks=None, t_start=time.monotonic(),
                             exact=False)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 20 and out["failed"] == 0
    assert out["metrics"]["chat.window_compiles"]["value"] == 0


def test_compile_counter_counts_new_programs():
    import jax.numpy as jnp
    counter = bench_run.CompileCounter()
    try:
        jax.jit(lambda x: x * 3)(jnp.ones(3))
        assert counter.n == 0                  # not active: not counted
        counter.active = True
        jax.jit(lambda x: x * 5)(jnp.ones(3))
        assert counter.n >= 1
    finally:
        counter.close()
