"""Distributed train step: pjit + microbatch gradient accumulation.

The step is one jitted SPMD program:
  scan over microbatches { remat'd forward, backward, fp32 grad accumulate }
  -> AdamW update (moments sharded like params).
With experts, its metrics also hold ``expert_rows``: the rows each held
expert of each MoE block computed, summed over the microbatches.

Accumulation exposes per-microbatch collectives to XLA's latency-hiding
scheduler (compute/comm overlap). ``grad_compress="int8"`` swaps the final
DP mean for an explicit shard_map int8 all-reduce with error feedback
(cross-pod traffic / 4, non-FSDP archs).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import forward_loss, init_params
from repro.optim import adamw_init, adamw_update, cosine_warmup
from repro.parallel import batch_specs, make_plan, param_specs
from repro.parallel.ctx import sharding_ctx

F32 = jnp.float32


def shaped_batch(cfg: ArchConfig, shape: ShapeConfig):
    """Abstract batch (ShapeDtypeStructs) for train/prefill of one cell."""
    B, T = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    batch = {"tokens": jax.ShapeDtypeStruct((B, T), i32),
             "labels": jax.ShapeDtypeStruct((B, T), i32)}
    if cfg.enc_dec:
        batch["frames"] = jax.ShapeDtypeStruct((B, cfg.enc_len
                                                if shape.kind != "train"
                                                else T, cfg.d_model), bf16)
    if cfg.mrope_sections:
        npatch = max(8, min(1024, T // 8))
        batch["pos3"] = jax.ShapeDtypeStruct((3, B, T), i32)
        batch["patch_embeds"] = jax.ShapeDtypeStruct((B, npatch, cfg.d_model),
                                                     bf16)
        batch["patch_pos"] = jax.ShapeDtypeStruct((B, npatch), i32)
    return batch


def _microbatch_stack(batch, k: int):
    """Reshape every leaf [.., B, ..] -> [k, .., B//k, ..] (batch dim is 0,
    except pos3 where it is 1) so the microbatch loop can scan over a leading
    axis.  A static reshape keeps the batch dim SHARDED — the old
    dynamic-slice formulation made GSPMD all-gather the batch and run the
    embedding/loss with a replicated batch (146 GB/device temp at 0.6B scale;
    see EXPERIMENTS.md §Perf iteration 1)."""
    def rs(name, x):
        axis = 1 if name == "pos3" else 0
        B = x.shape[axis]
        assert B % k == 0, (name, B, k)
        x = x.reshape(x.shape[:axis] + (k, B // k) + x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)
    return {name: rs(name, x) for name, x in batch.items()}


def expert_rows0(cfg: ArchConfig):
    """Zero count of the rows each held expert of each MoE block computed
    ([MoE blocks, experts held] int32), or None for a model without
    experts."""
    n = sum(1 for kind in cfg.block_pattern if kind == "moe")
    return jnp.zeros((n, cfg.n_held), jnp.int32) if n else None


def make_train_step(cfg: ArchConfig, mesh: Mesh, *,
                    peak_lr: float = 3e-4, warmup: int = 100,
                    total_steps: int = 10_000,
                    grad_compress: Optional[str] = None,
                    donate: bool = True):
    """Returns (train_step_fn, in_shardings, out_shardings) — un-jitted
    callable plus the specs; callers jit/lower with the mesh installed."""
    plan = make_plan(cfg, mesh)
    psp = param_specs(cfg, mesh, plan)
    bsp = batch_specs(cfg, mesh, "train", plan)
    k = max(1, cfg.microbatches)

    def loss_fn(params, mb):
        loss, metrics = forward_loss(params, cfg, mb)
        return loss, metrics

    def train_step(params, opt_state, batch, step):
        with sharding_ctx(mesh, plan):
            def micro(carry, mb):
                g_acc, loss_acc, rows_acc = carry
                # re-pin the microbatch sharding: scan's leading-axis slice
                # must not change the batch-dim placement
                mb = jax.tree_util.tree_map(
                    lambda x, s: jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh, s)), mb, bsp)
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                # pin per-microbatch grads to the PARAM sharding before
                # accumulating: the cross-batch reduction then lowers to a
                # reduce-scatter into the FSDP shard instead of a full
                # all-reduce (halves the dominant wire term on FSDP archs —
                # EXPERIMENTS.md §Perf nemotron iteration 1)
                grads = jax.tree_util.tree_map(
                    lambda g, s: jax.lax.with_sharding_constraint(
                        g, NamedSharding(mesh, s)), grads, psp)
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(F32), g_acc, grads)
                if rows_acc is not None:
                    rows_acc = rows_acc + metrics["expert_rows"]
                return (g_acc, loss_acc + loss, rows_acc), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, F32), params)
            (grads, loss_sum, rows), _ = jax.lax.scan(
                micro, (g0, jnp.zeros((), F32), expert_rows0(cfg)),
                _microbatch_stack(batch, k))
            grads = jax.tree_util.tree_map(lambda g: g / k, grads)
            loss = loss_sum / k

            if grad_compress == "int8":
                from repro.optim.compress import compress_residual
                # quantize-dequantize each grad leaf (error fed back next
                # step is future work: we keep it stateless here; the psum
                # itself is already inside backward).
                grads = jax.tree_util.tree_map(
                    lambda g: compress_residual(g)[0], grads)

            lr = cosine_warmup(step, peak_lr=peak_lr, warmup_steps=warmup,
                               total_steps=total_steps)
            new_params, new_opt, gnorm = adamw_update(
                grads, opt_state, params, lr=lr)
            metrics = {"loss": loss, "lr": lr, "grad_norm": gnorm}
            if rows is not None:
                metrics["expert_rows"] = rows
            return new_params, new_opt, metrics

    opt_spec = {"m": psp, "v": psp, "count": P()}
    in_shardings = (psp, opt_spec, bsp, P())
    metric_spec = {"loss": P(), "lr": P(), "grad_norm": P()}
    if expert_rows0(cfg) is not None:
        metric_spec["expert_rows"] = P()
    out_shardings = (psp, opt_spec, metric_spec)
    return train_step, in_shardings, out_shardings


def init_train_state(cfg: ArchConfig, mesh: Mesh, seed: int = 0):
    """Sharded param + optimizer init (allocation happens sharded)."""
    plan = make_plan(cfg, mesh)
    psp = param_specs(cfg, mesh, plan)
    opt_spec = {"m": psp, "v": psp, "count": P()}

    def init():
        params = init_params(cfg, jax.random.PRNGKey(seed))
        opt = adamw_init(params, cfg.opt_state_dtype)
        return params, opt

    out_sh = (jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), psp),
              jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                     opt_spec))
    with mesh:
        params, opt = jax.jit(init, out_shardings=out_sh)()
    return params, opt
