"""Dense MLP (gated SwiGLU / ungated squared-ReLU / GELU) and a drop-free
MoE layer.

The MoE layer routes each token over all ``n_experts`` (softmax or sigmoid
scores; a selection bias may take part in the choice only), and computes
the experts held here (``experts_held``, a chip's share under expert
parallelism; all by default) on exactly the rows routed to them: the
(token, choice) rows are sorted by expert and each held expert's group runs
through a grouped matrix product, ``jax.lax.ragged_dot``, which the TPU
runs as a Mosaic kernel of its own name. No token is ever dropped, and
training, prefill and decode take the same path. Shared
experts, where the config has them, are one dense SwiGLU beside the routed
part. What experts held elsewhere add is left out: under expert
parallelism it arrives from the other chips.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, activation_fn, dense_init, matmul


# --------------------------------------------------------------------------
# dense MLP
# --------------------------------------------------------------------------
def init_mlp_params(key, cfg, dtype, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], cfg.d_model, d_ff, dtype),
         "w_down": dense_init(ks[1], d_ff, cfg.d_model, dtype)}
    if cfg.gated_mlp:
        p["w_gate"] = dense_init(ks[2], cfg.d_model, d_ff, dtype)
    return p


def mlp_forward(p, cfg, x):
    act = activation_fn(cfg.activation)
    up = matmul(x, p["w_up"])
    if "w_gate" in p:
        h = act(matmul(x, p["w_gate"]).astype(F32)).astype(x.dtype) * up
    else:
        h = act(up.astype(F32)).astype(x.dtype)
    return matmul(h, p["w_down"])


# --------------------------------------------------------------------------
# MoE
# --------------------------------------------------------------------------
def init_moe_params(key, cfg, dtype):
    E, d, f = cfg.n_held, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, cfg.n_experts, jnp.float32),
        "w_up": (jax.random.normal(ks[1], (E, d, f), F32) / d ** 0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (E, f, d), F32) / f ** 0.5).astype(dtype),
    }
    if cfg.gated_mlp:
        p["w_gate"] = (jax.random.normal(ks[3], (E, d, f), F32) / d ** 0.5).astype(dtype)
    if cfg.topk_method == "noaux_tc":
        p["e_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp_params(ks[4], cfg, dtype,
                                      cfg.n_shared_experts * cfg.d_ff_expert)
    return p


def route(p, cfg, xf):
    """Router over all experts: (chosen experts [N, K], their weights
    [N, K] f32, normalised to sum 1 and scaled, load-balance aux loss)."""
    E, K = cfg.n_experts, cfg.top_k
    logits = matmul(xf, p["router"].astype(xf.dtype), out_dtype=F32)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores
    if "e_bias" in p:
        # the bias chooses, the unbiased scores weigh
        choice = scores + jax.lax.stop_gradient(p["e_bias"].astype(F32))
    _, top_e = jax.lax.top_k(choice, K)                               # [N, K]
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * cfg.routed_scaling
    aux = jnp.zeros((), F32)
    if cfg.router_aux_coef:
        # Switch-style load balance over the normalised scores
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        assign = jnp.zeros((E,), F32).at[top_e.reshape(-1)].add(1.0)
        aux = (E * jnp.sum(jnp.mean(probs, axis=0) * assign)
               / top_e.size * cfg.router_aux_coef)
    return top_e, top_w, aux


def grouped_matmul(rows, w, group_sizes):
    """rows [M, k] sorted by group, w [G, k, n] -> [M, n]; rows past the
    groups' total are not defined (the caller masks them). On the TPU,
    ragged_dot is a Mosaic kernel (``ragged-dot-none.N`` in a trace)."""
    return jax.lax.ragged_dot(rows, w, group_sizes,
                              preferred_element_type=F32).astype(rows.dtype)


def moe_forward(p, cfg, x, first_expert: int = 0):
    """x: [B, T, d] -> (y, aux_loss, rows [experts held] int32).

    The held experts are ``first_expert`` .. ``first_expert +
    cfg.n_held - 1``; ``rows`` counts the (token, choice) rows each of them
    computed.
    """
    B, T, d = x.shape
    N, K, Eh = B * T, cfg.top_k, cfg.n_held
    xf = x.reshape(N, d)
    top_e, top_w, aux = route(p, cfg, xf)

    # (token, choice) rows of held experts first, sorted by expert; the
    # others after them, where no product reaches
    local = top_e.reshape(-1) - first_expert
    key = jnp.where((local >= 0) & (local < Eh), local, Eh)
    order = jnp.argsort(key, stable=True)
    group_sizes = jnp.zeros((Eh + 1,), jnp.int32).at[key].add(1)[:Eh]
    valid = (jnp.arange(N * K) < jnp.sum(group_sizes))[:, None]
    token = order // K
    rows = jnp.where(valid, xf[token], 0)

    act = activation_fn(cfg.activation)
    if "w_gate" in p:
        # gate and up in one product: one kernel, twice as wide
        f = p["w_up"].shape[-1]
        gate_up = grouped_matmul(
            rows, jnp.concatenate([p["w_gate"], p["w_up"]], -1), group_sizes)
        h = act(gate_up[:, :f].astype(F32)).astype(x.dtype) * gate_up[:, f:]
    else:
        up = grouped_matmul(rows, p["w_up"], group_sizes)
        h = act(up.astype(F32)).astype(x.dtype)
    y = grouped_matmul(h, p["w_down"], group_sizes)
    # masked before the weighting: the weight's gradient then never meets
    # an undefined row
    y = jnp.where(valid, y.astype(F32), 0) * top_w.reshape(-1)[order][:, None]
    out = jnp.zeros((N, d), F32).at[token].add(y)
    if "shared" in p:
        out = out + mlp_forward(p["shared"], cfg, xf).astype(F32)
    return out.astype(x.dtype).reshape(B, T, d), aux, group_sizes
