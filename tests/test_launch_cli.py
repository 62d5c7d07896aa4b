"""Launch CLIs and chip_smoke.py on the CPU: what they resolve and refuse.

Nothing here compiles for, or runs on, a chip; chip_smoke.py does that.
"""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config
from repro.launch import serve, train

REPO = Path(__file__).resolve().parents[1]


def _run(args, **env):
    """Run python ``args`` from the repo root on the CPU; returns the
    CompletedProcess."""
    full = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    return subprocess.run([sys.executable, *args], env=full, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("extra,want", [
    ([], get_config("qwen3-0.6b")),
    (["--reduced"], get_config("qwen3-0.6b").reduced()),
], ids=["full", "reduced"])
def test_train_cli_resolves_config_on_one_device(extra, want, tmp_path):
    """One device no longer implies the toy config: only --reduced does."""
    cfg, mesh, shape = train.resolve(train.parse_args(
        ["--arch", "qwen3-0.6b", "--ckpt-dir", str(tmp_path), *extra]))
    assert cfg == want
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert (shape.seq_len, shape.global_batch) == (
        (64, 8) if extra else (2048, 2))


def test_train_cli_requires_ckpt_dir():
    """The trainer resumes from whatever its checkpoint directory holds, so
    the CLI has no shared default directory to resume from."""
    with pytest.raises(SystemExit):
        train.parse_args(["--arch", "qwen3-0.6b"])


@pytest.mark.parametrize("external", [None, "elsewhere/cache"])
def test_compile_cache_dir(external, tmp_path):
    """An external JAX_COMPILATION_CACHE_DIR is left alone; otherwise the
    cache goes to the fixed <repo>/.jax_cache."""
    env = {} if external is None else {
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / external)}
    r = _run(["-c", "import jax\n"
              "from repro.launch.compile_cache import enable_compile_cache\n"
              "print(enable_compile_cache())\n"
              "print(jax.config.jax_compilation_cache_dir)"], **env)
    assert r.returncode == 0, r.stderr
    want = str(REPO / ".jax_cache") if external is None else str(
        tmp_path / external)
    assert r.stdout.split() == [want, want]


def test_chip_smoke_fails_without_tpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_phases_rehearse_on_cpu(monkeypatch):
    """chip_smoke.py's train and serve phases, end to end through the CLIs,
    at the reduced config (the chip runs the same code at full width)."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    for mod in (train, serve):       # keep the test process's cache off
        monkeypatch.setattr(mod, "enable_compile_cache", lambda: None)
    cs.train_phase(["--reduced", "--seq", "64", "--batch", "4"])
    cs.serve_phase(["--reduced", "--max-seq", "128"])
