"""AdamW from scratch (no optax): pytree-native, dtype-configurable moments.

Moments inherit the parameter sharding (the optimizer state spec tree is the
param spec tree), so FSDP'd params get FSDP'd m/v for free.  ``opt_state_dtype``
= bfloat16 halves optimizer HBM for the 340B config (see DESIGN.md §5).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.common import dtype_of

F32 = jnp.float32
# leaves that weight decay leaves alone, by name: norm gains, biases, the
# router's selection bias and the SSMs' per-channel parameters. Every other
# leaf is a matrix and decays.
NO_DECAY = frozenset({
    "ln1", "ln2", "ln_x", "final_norm", "enc_norm", "norm", "gn", "ff_ln",
    "q_norm", "k_norm", "kv_a_norm", "bq", "bk", "bv", "e_bias", "A_log",
    "D", "dt_bias", "conv_b", "b", "b_i", "b_f"})


def leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def adamw_init(params, dtype: str = "float32"):
    dt = dtype_of(dtype)
    zeros = lambda p: jnp.zeros(p.shape, dt)
    return {
        "m": jax.tree_util.tree_map(zeros, params),
        "v": jax.tree_util.tree_map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }


def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree_util.tree_leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(F32))) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree_util.tree_map(
        lambda g: (g.astype(F32) * scale).astype(g.dtype), grads), gn


def adamw_update(grads, opt_state, params, *, lr, b1=0.9, b2=0.95,
                 eps=1e-8, weight_decay=0.1, max_grad_norm=1.0):
    """Returns (new_params, new_opt_state, grad_norm)."""
    grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
    count = opt_state["count"] + 1
    c1 = 1.0 - b1 ** count.astype(F32)
    c2 = 1.0 - b2 ** count.astype(F32)

    def upd(g, m, v, p, decay):
        g32 = g.astype(F32)
        m32 = m.astype(F32) * b1 + g32 * (1 - b1)
        v32 = v.astype(F32) * b2 + jnp.square(g32) * (1 - b2)
        step = (m32 / c1) / (jnp.sqrt(v32 / c2) + eps)
        # decoupled weight decay on matrices only (by leaf name)
        if decay:
            step = step + weight_decay * p.astype(F32)
        new_p = (p.astype(F32) - lr * step).astype(p.dtype)
        return new_p, m32.astype(m.dtype), v32.astype(v.dtype)

    paths, tdef = jax.tree_util.tree_flatten_with_path(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_m = tdef.flatten_up_to(opt_state["m"])
    flat_v = tdef.flatten_up_to(opt_state["v"])
    out = [upd(g, m, v, p, leaf_name(path) not in NO_DECAY)
           for g, m, v, (path, p) in zip(flat_g, flat_m, flat_v, paths)]
    new_p = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(tdef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "count": count}, gnorm
