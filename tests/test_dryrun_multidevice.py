"""Multi-device sharding smoke (subprocess: forces 8 host devices).

The production dry-run (512 devices, full configs) runs via
``python -m repro.launch.dryrun`` — here we verify the same machinery
end-to-end on an 8-device (2, 4) mesh with REDUCED configs, cheap enough
for the test suite, and that sharded buffers really are distributed.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch.hloparse import xla_cost_dict
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_step
from repro.train.step import init_train_state, make_train_step
from repro.data.pipeline import SyntheticLM

assert len(jax.devices()) == 8
mesh = make_host_mesh(2, 4)

# 1) lower+compile one reduced cell per family through build_step
for arch in ["qwen3_0_6b", "zamba2_2_7b", "mixtral_8x22b"]:
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              d_model=128, n_heads=4, n_kv_heads=4,
                              head_dim=32, vocab_size=256, block_pattern=())
    shape = ShapeConfig("t", 64, 8, "train")
    spec = build_step(cfg, shape, mesh)
    wrap = lambda s: jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, x), s)
    with mesh:
        compiled = jax.jit(spec.fn, in_shardings=wrap(spec.in_shardings),
                           out_shardings=wrap(spec.out_shardings),
                           donate_argnums=spec.donate).lower(
                               *spec.args).compile()
    assert xla_cost_dict(compiled)["flops"] > 0
    print("ok", arch)

# 2) actually EXECUTE a sharded train step and check distribution + loss
cfg = dataclasses.replace(get_config("qwen3_0_6b").reduced(),
                          n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                          head_dim=32, d_ff=256, vocab_size=256,
                          block_pattern=(), remat="none",
                          param_dtype="float32")
params, opt = init_train_state(cfg, mesh)
emb_shards = {s.device.id for s in params["embed"].addressable_shards}
assert len(emb_shards) == 8, emb_shards          # vocab+fsdp sharded
step_fn, in_sh, out_sh = make_train_step(cfg, mesh, peak_lr=1e-2)
with mesh:
    jit_step = jax.jit(step_fn,
                       in_shardings=jax.tree_util.tree_map(
                           lambda s: NamedSharding(mesh, s), in_sh),
                       out_shardings=jax.tree_util.tree_map(
                           lambda s: NamedSharding(mesh, s), out_sh),
                       donate_argnums=(0, 1))
    src = SyntheticLM(cfg.vocab_size, 32, 8, seed=0)
    losses = []
    for i in range(8):
        b = {k: jnp.asarray(v) for k, v in src.batch(0).items()}
        params, opt, m = jit_step(params, opt, b, jnp.int32(i))
        losses.append(float(m["loss"]))
assert np.isfinite(losses).all()
assert losses[-1] < losses[0], losses            # learning on 8 devices
print("ok sharded-exec", losses[0], "->", losses[-1])

# 3) context-parallel shard_map attention: loss/grads must match the
#    unsharded single-device reference EXACTLY (same math, fp32)
from repro.models import forward_loss, init_params
from repro.parallel import make_plan, param_specs, batch_specs
from repro.parallel.ctx import sharding_ctx
cfg = dataclasses.replace(get_config("qwen2_1_5b").reduced(),
                          n_layers=2, d_model=96, n_heads=6, n_kv_heads=2,
                          head_dim=16, d_ff=128, vocab_size=64,
                          block_pattern=(), remat="none",
                          param_dtype="float32")
plan = make_plan(cfg, mesh)
assert plan.context_parallel              # 6 heads % 4 != 0
params = init_params(cfg, jax.random.PRNGKey(7))
rngb = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rngb.integers(0, 64, (8, 32)), jnp.int32),
         "labels": jnp.asarray(rngb.integers(0, 64, (8, 32)), jnp.int32)}

def loss_fn(p, b):
    return forward_loss(p, cfg, b)[0]

ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, batch)  # no ctx

psp = param_specs(cfg, mesh, plan)
bsp = batch_specs(cfg, mesh, "train", plan, batch=8)
def sharded_loss(p, b):
    with sharding_ctx(mesh, plan):       # enables the shard_map CP path
        return forward_loss(p, cfg, b)[0]
with mesh:
    sh_loss, sh_grads = jax.jit(
        jax.value_and_grad(sharded_loss),
        in_shardings=(jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), psp),
            jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), bsp)))(params, batch)
np.testing.assert_allclose(float(sh_loss), float(ref_loss),
                           rtol=1e-5, atol=1e-6)
flat_r = jax.tree_util.tree_leaves(ref_grads)
flat_s = jax.tree_util.tree_leaves(sh_grads)
for r, s in zip(flat_r, flat_s):
    np.testing.assert_allclose(np.asarray(s), np.asarray(r),
                               rtol=5e-4, atol=5e-5)
print("ok cp-shardmap-grads", float(sh_loss))
"""


@pytest.mark.slow
def test_multidevice_dryrun_and_exec():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "ok sharded-exec" in r.stdout
