"""Architectures enter the benchmark as files: chipbench/archs/<name>.py,
named by a configuration's ``bench_arch``, and chipbench/drive_<kind>.py,
named by a traffic file's ``kind``.

The numbers pinned here were read before the dense decoder's layout,
reference and counts moved out of weights.py, reference.py and flops.py
into archs/qwen_dense.py: the same seed still gives the same bits, and
the reference and the counts the same numbers.
"""
from __future__ import annotations

import ast
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, program_trace, reference, spec, traffic_gen
from chipbench import weights
from chipbench.test_chipbench_harness import TINY

CONFIGS = ("qwen3-0.6b", "qwen2-1.5b")
# float64 sum of each leaf of weights.make for TINY from seed 3
LEAF_SUMS = {
    "qwen3-0.6b": {
        "embed": -3.7628852147608995, "final_norm": 63.875,
        "k_norm": 31.79296875, "ln1": 128.2265625, "ln2": 127.046875,
        "q_norm": 31.34375, "w_down": 3.143916703760624,
        "w_gate": -2.273065060377121, "w_up": -3.9970905780792236,
        "wk": -12.844599962234497, "wo": -0.42041851580142975,
        "wq": 2.698512315750122, "wv": 8.415711969137192},
    "qwen2-1.5b": {
        "bk": -1.205322265625, "bq": 0.590118408203125,
        "bv": -0.5471038818359375, "embed": -0.716515839099884,
        "final_norm": 63.47265625, "ln1": 127.11328125,
        "ln2": 127.73046875, "w_down": -0.8196247294545174,
        "w_gate": -3.9970905780792236, "w_up": -8.950280368328094,
        "wk": 5.421184986829758, "wo": 1.349256157875061,
        "wq": 1.8656178787350655, "wv": -6.255962491035461},
}
# reference on those weights, tokens train_batch(3, 0, 2, 64): loss, sum of
# the per-leaf gradient norms, widest served gap and control gap of 16
# positions of row 0
REFERENCE = {"qwen3-0.6b": (5.565880298614502, 4.345293281599879,
                            4.507605075836182, 0.2523081302642822),
             "qwen2-1.5b": (5.572087287902832, 3.4225677888607606,
                            4.696151256561279, 0.3000792860984802)}
# full-size train_step(2, 2048), prefill(517), decode_token(1234),
# decode_step_bytes([3, 0, 2047])
COUNTS = {"qwen3-0.6b": (17533130244096.0, 486341591040.0, 1475018752.0,
                         1427554304),
          "qwen2-1.5b": (40099425288192.0, 1378200244224.0, 3299426304.0,
                         3146292224)}
DENSE_ONLY = re.compile(r"\b(wq|wk|wv|w_gate|w_up|w_down|head_dim"
                        r"|intermediate_size|num_key_value_heads)\b")


def _config(name, **extra):
    return {**spec.load_json(spec.HERE / "configs" / f"{name}.json"),
            **extra}


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_keep_their_bits(name):
    w = weights.maker(_config(name, **TINY))(weights.seed_key(3))
    sums = {k: float(np.sum(np.asarray(v, np.float64)))
            for k, v in w.items()}
    assert sums == LEAF_SUMS[name]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_keeps_its_numbers(name):
    c = _config(name, **TINY)
    w = weights.maker(c)(weights.seed_key(3))
    tokens = jnp.asarray(traffic_gen.train_batch(3, 0, 2, 64, 256))
    seq = np.zeros((1, 64), np.int32)
    seq[0, :40] = np.asarray(tokens[0, :40])
    with jax.default_matmul_precision("highest"):
        lval, g = jax.value_and_grad(reference.loss)(w, c, tokens)
        gap, ctrl = reference.served_gaps(
            w, reference.items(c), seq, np.arange(20, 36, dtype=np.int32),
            np.asarray(tokens[0, 21:37]), True)
    norms = weights.flat_norms(weights.leaf_norms(g, c), c)
    got = (float(lval), sum(norms.values()), float(np.max(gap)),
           float(np.max(ctrl)))
    assert got == pytest.approx(REFERENCE[name], rel=1e-6)


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_of_the_full_configs(name):
    c = _config(name)
    assert (flops.train_step(c, 2, 2048), flops.prefill(c, 517),
            flops.decode_token(c, 1234),
            flops.decode_step_bytes(c, [3, 0, 2047])) == COUNTS[name]


def test_only_the_dense_module_names_dense_leaves_and_keys():
    # an arch module may name what its family has: another family's config
    # (DeepseekV3's) carries intermediate_size and num_key_value_heads too
    named = []
    for path in sorted(spec.HERE.rglob("*.py")):
        rel = path.relative_to(spec.HERE).as_posix()
        if rel.startswith("archs/") or path.name.startswith("test_"):
            continue
        if DENSE_ONLY.search(path.read_text()):
            named.append(rel)
    assert named == []


@pytest.mark.parametrize("name", CONFIGS)
def test_a_config_names_its_arch_module(name):
    c = _config(name)
    assert spec.arch(c).__file__ == str(spec.HERE / "archs"
                                        / f"{c['bench_arch']}.py")
    with pytest.raises(KeyError):
        spec.arch({k: v for k, v in c.items() if k != "bench_arch"})
    with pytest.raises(KeyError, match="qwen_dense"):
        spec.arch({**c, "bench_arch": "no_such_family"})


def _kinds_named(error):
    """The kinds that run.driver's ValueError lists as found."""
    return ast.literal_eval(re.search(r"have (\[.*\])", error).group(1))


def test_drivers_are_found_by_file():
    from chipbench import drive_serve, drive_train
    from chipbench.run import driver
    assert driver("train") is drive_train
    assert driver("serve") is drive_serve
    with pytest.raises(ValueError) as e:
        driver("no_such_kind")
    kinds = _kinds_named(str(e.value))
    assert {"serve", "train"} <= set(kinds)
    assert kinds == sorted(p.stem[len("drive_"):]
                           for p in spec.HERE.glob("drive_*.py"))


def test_traced_run_reads_the_program_reduction(tmp_path, monkeypatch):
    # the CPU's trace has no device plane: the run reduces a recorded chat
    # trace in its place, as it would its own on the chip
    import gzip
    import time
    from chipbench import run as bench_run
    from chipbench.test_chipbench_harness import SERVE_TINY, tiny_cell
    recorded = tmp_path / "chat.xplane.pb"
    recorded.write_bytes(gzip.decompress(
        (spec.HERE / "testdata" / "program_trace.xplane.pb.gz")
        .read_bytes()))
    plain = bench_run.Tracer.reduce_file
    monkeypatch.setattr(bench_run.Tracer, "reduce_file",
                        lambda self, path: plain(self, str(recorded)))
    cell = tiny_cell("qwen3-0.6b.chat", rate_per_s=20.0, **SERVE_TINY)
    cell.per_layer = spec.load_cell("qwen3-0.6b.chat").per_layer
    out = bench_run.run_cell(cell, 5, 0.5, True, devices=jax.devices()[:1],
                             peaks=None, t_start=time.monotonic(),
                             exact=False)
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    decode = out["metrics"]["chat.decode_device_ms"]
    assert decode["unit"] == "ms" and 15 < decode["value"] < 30
    assert out["device"]["busy_s"] > 0


def test_decode_device_ms_needs_a_trace():
    from chipbench.record import Run
    run = Run(None, 1, 1, None)
    assert spec.reader("chat.decode_device_ms")(run) is None
    assert program_trace.numbers(None) == {}


# Run in a copy of chipbench/ and BENCHMARK.json to which only files and
# JSON entries were added: a second arch module (qwen_dense under another
# name), a configuration that names it, two cells on it with their
# limits, and a driver of a new kind.
PLUGIN = textwrap.dedent('''
    import json, sys, time
    sys.path[:0] = [sys.argv[1], sys.argv[2]]
    import jax
    from chipbench import run, spec
    from chipbench.test_chipbench_harness import SERVE_TINY, TINY
    what = sys.argv[3]
    loaded, load = [], spec._load
    spec._load = lambda kind, name, path: (loaded.append((kind, name))
                                           or load(kind, name, path))
    if what == "driver":
        d = run.driver("echo")
        try:
            run.driver("no_such_kind")
        except ValueError as e:
            print(json.dumps({"echo": d.ECHO, "error": str(e)}))
        sys.exit(0)
    cell = spec.load_cell(f"tiny-copy.{what}")
    cell.config = {**cell.config, **TINY,
                   "program": {**cell.config["program"], "remat": "none"}}
    if what == "chat":
        cell.traffic = {**cell.traffic, "rate_per_s": 20.0, **SERVE_TINY}
    else:
        cell.traffic = {**cell.traffic, "seq_len": 64}
    out = run.run_cell(cell, 2**31 + 17, 0.5, False,
                       devices=jax.devices()[:1], peaks=None,
                       t_start=time.monotonic(), exact=False)
    out["arch_file"] = spec.arch(cell.config).__file__
    out["archs"] = sorted({n for kind, n in loaded if kind == "arch"})
    print(json.dumps(out))
''')


def _copy_with_plugins(root):
    """The copy, with files and JSON entries added and none changed."""
    shutil.copy(spec.BENCHMARK, root / "BENCHMARK.json")
    here = root / "chipbench"
    shutil.copytree(spec.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(here / "archs" / "qwen_dense.py",
                here / "archs" / "qwen_copy.py")
    config = spec.load_json(here / "configs" / "qwen3-0.6b.json")
    config["bench_arch"] = "qwen_copy"
    (here / "configs" / "tiny-copy.json").write_text(json.dumps(config))
    (here / "drive_echo.py").write_text('ECHO = "found by file"\n')
    bench = spec.load_json(root / "BENCHMARK.json")
    entry = next(e for e in bench["configs"] if e["name"] == "qwen3-0.6b")
    bench["configs"].append({**entry, "name": "tiny-copy",
                             "file": "chipbench/configs/tiny-copy.json"})
    for w in [w for w in bench["workloads"] if w["config"] == "qwen3-0.6b"]:
        cell = w["name"].replace("qwen3-0.6b", "tiny-copy")
        bench["workloads"].append({**w, "name": cell, "config": "tiny-copy"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if w["name"] in m.get("workloads", []):
                m["workloads"].append(cell)
        shutil.copy(here / "cells" / f"{w['name']}.json",
                    here / "cells" / f"{cell}.json")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def _run_in_copy(root, what):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "-c", PLUGIN, str(root), str(spec.REPO / "src"),
         what], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("what", ["train", "chat"])
def test_a_new_arch_enters_by_files_alone(tmp_path, what):
    _copy_with_plugins(tmp_path)
    out = _run_in_copy(tmp_path, what)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["arch_file"] == str(tmp_path / "chipbench" / "archs"
                                   / "qwen_copy.py")
    assert out["archs"] == ["qwen_copy"]


def test_a_new_driver_is_found_by_file(tmp_path):
    _copy_with_plugins(tmp_path)
    out = _run_in_copy(tmp_path, "driver")
    assert out["echo"] == "found by file"
    assert {"echo", "serve", "train"} <= set(_kinds_named(out["error"]))


# What a later configuration brings: its family's arch module, which names
# its own leaves and keys, a driver of a new kind, and a cell on another
# configuration in BENCHMARK.json.
LATER_ARCH = '''"""A family whose config carries head_dim,
intermediate_size and num_key_value_heads, with leaves wq, wk, wv, w_gate,
w_up, w_down."""
KEYS = ("head_dim", "intermediate_size", "num_key_value_heads")
'''


def test_later_plugins_leave_these_tests_passing(tmp_path):
    shutil.copy(spec.BENCHMARK, tmp_path / "BENCHMARK.json")
    here = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (here / "archs" / "later_family.py").write_text(LATER_ARCH)
    (here / "drive_later.py").write_text('"""Drives kind later."""\n')
    bench = spec.load_json(tmp_path / "BENCHMARK.json")
    file = "chipbench/configs/qwen2-1.5b.json"
    bench["configs"].append({**bench["configs"][0], "name": "qwen2-1.5b",
                             "file": file, "source": spec.load_json(
                                 tmp_path / file)["source"]})
    bench["workloads"].append({**bench["workloads"][0],
                               "name": "qwen2-1.5b.later",
                               "config": "qwen2-1.5b", "traffic": "later"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(spec.REPO / "src")}
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "chipbench/test_chipbench_archs.py", "-k",
         "dense_leaves or found_by_file or names_its_arch"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-4000:]
    assert "5 passed" in p.stdout.splitlines()[-1], p.stdout[-2000:]
