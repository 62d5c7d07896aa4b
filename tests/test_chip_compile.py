"""Compile the chip path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
kernel tiling Mosaic cannot lower, or a program larger than the chip's
HBM. Each test compiles one program of ``chip_smoke.py``'s path at
qwen3-0.6b's published widths against one chip of a ``v5e:2x2`` topology.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every test worker imports this file.
"""
from __future__ import annotations

import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.kernels.flash_attention import flash_attention
from repro.models import abstract_params, init_cache
from repro.optim import adamw_init
from repro.serve.engine import make_decode_fn
from repro.train.step import make_train_step, shaped_batch

V5E_HBM = 15.75 * 2**30          # what the v5e compiler lets one program use
CFG = get_config("qwen3_0_6b")


def _chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("B,T", [(1, 2048), (2, 4096)])
def test_flash_attention_compiles_to_mosaic(one_chip, B, T):
    H, KV, hd = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    q = jax.ShapeDtypeStruct((B, T, H, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, T, KV, hd), jnp.bfloat16, sharding=one_chip)
    compiled = flash_attention.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_compiles_for_one_chip(one_chip):
    cs = _chip_smoke()
    slots, max_seq = cs.SERVE_SLOTS, cs.SERVE_MAX_SEQ
    cache = jax.eval_shape(lambda: init_cache(CFG, slots, max_seq))
    args = (_on(abstract_params(CFG), one_chip),
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
            _on(cache, one_chip),
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip))
    compiled = make_decode_fn(CFG).lower(*args).compile()
    assert _program_bytes(compiled) < V5E_HBM


def test_train_step_fits_one_chip(topo):
    """chip_smoke.py's train batch, with AdamW state, in one chip's HBM."""
    cs = _chip_smoke()
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    fn, in_sh, out_sh = make_train_step(CFG, mesh)
    named = lambda tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree)
    params = abstract_params(CFG)
    opt = jax.eval_shape(lambda: adamw_init(params, CFG.opt_state_dtype))
    batch = shaped_batch(CFG, ShapeConfig("chip-smoke", cs.TRAIN_SEQ,
                                          cs.TRAIN_BATCH, "train"))
    with mesh:
        compiled = jax.jit(fn, in_shardings=named(in_sh),
                           out_shardings=named(out_sh),
                           donate_argnums=(0, 1)).lower(
            params, opt, batch, jax.ShapeDtypeStruct((), jnp.int32)).compile()
    used = _program_bytes(compiled)
    assert used < V5E_HBM, f"{used / 2**30:.2f} GiB"
