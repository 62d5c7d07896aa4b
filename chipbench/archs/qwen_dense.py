"""The dense Qwen2 / Qwen3 decoder: what the benchmark knows of its shape.

A configuration file names this module with ``"bench_arch": "qwen_dense"``.
Weight layout (per-layer leaves stacked on a leading axis):

    embed [V, d]   final_norm [d]   (lm_head [V, d] when untied)
    ln1 ln2 [L, d]   wq [L, d, H*hd]   wk wv [L, d, KV*hd]   wo [L, H*hd, d]
    bq bk bv [L, width]   (attention_bias)   q_norm k_norm [L, hd] (qk_norm)
    w_gate w_up [L, d, F]   w_down [L, F, d]

``to_program`` / ``from_program`` rename these to and from the program's
parameter tree (repro.models.model.init_params). ``hidden`` is the float32
reference forward, written from the published description (Qwen2
technical report, arXiv:2407.10671; the Qwen3 model card and config.json),
not from the program: RMSNorm, GQA, rotary embeddings on halves, Qwen3's
per-head RMSNorm of q and k, Qwen2's q/k/v biases, SwiGLU. The counts at
the end are what ``flops.py`` builds its FLOPs and bytes from.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference import F32, attention, einsum, rms, rope

# config.json keys of this family -> the program's ArchConfig fields,
# beside spec.ARCH_FIELDS
ARCH_FIELDS = {
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "attention_bias": "qkv_bias",
    "qk_norm": "qk_norm",
}
# the configuration's keys that the reference reads
REF_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "rms_norm_eps", "rope_theta", "tie_word_embeddings",
            "attention_bias", "qk_norm", "param_dtype")
STACKED = ("ln1", "wq", "wk", "wv", "wo", "bq", "bk", "bv", "q_norm",
           "k_norm", "ln2", "w_gate", "w_up", "w_down")
# leaves AdamW decays
MATRICES = ("embed", "lm_head", "wq", "wk", "wv", "wo", "w_gate", "w_up",
            "w_down")
# norm gains, made as 1 + N(0, 0.1^2)
NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
ATTN = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "q_norm", "k_norm")
MLP = ("w_gate", "w_up", "w_down")


def shapes(c: Dict[str, Any]) -> Dict[str, tuple]:
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    hd, F = c["head_dim"], c["intermediate_size"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    s = {"embed": (V, d), "final_norm": (d,), "ln1": (L, d),
         "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
         "wo": (L, q, d), "ln2": (L, d), "w_gate": (L, d, F),
         "w_up": (L, d, F), "w_down": (L, F, d)}
    if not c["tie_word_embeddings"]:
        s["lm_head"] = (V, d)
    if c["attention_bias"]:
        s.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    if c["qk_norm"]:
        s.update(q_norm=(L, hd), k_norm=(L, hd))
    return s


def init_std(c: Dict[str, Any], name: str, shape) -> float:
    """Standard deviation of a leaf that is not a norm gain."""
    if name in ("embed", "lm_head"):
        return 0.02
    if name in ("bq", "bk", "bv"):
        return 0.1
    std = 1.0 / math.sqrt(shape[-2])
    if name in ("wo", "w_down"):
        std /= math.sqrt(2 * c["num_hidden_layers"])
    return std


def to_program(w: Dict[str, Any]) -> Dict[str, Any]:
    layer = {"ln1": w["ln1"], "ln2": w["ln2"],
             "attn": {k: w[k] for k in ATTN if k in w},
             "mlp": {k: w[k] for k in MLP}}
    p = {"embed": w["embed"], "final_norm": w["final_norm"],
         "stages": [layer]}
    if "lm_head" in w:
        p["lm_head"] = w["lm_head"]
    return p


def from_program(p: Dict[str, Any]) -> Dict[str, Any]:
    if len(p["stages"]) != 1:
        raise ValueError("expected one stage of identical dense blocks")
    st = p["stages"][0]
    w = {"embed": p["embed"], "final_norm": p["final_norm"],
         "ln1": st["ln1"], "ln2": st["ln2"], **st["attn"], **st["mlp"]}
    if "lm_head" in p:
        w["lm_head"] = p["lm_head"]
    return w


def _layer(c, mode, x, lw, pos):
    lw = {k: a.astype(F32) for k, a in lw.items()}
    eps, hd = c["rms_norm_eps"], c["head_dim"]
    B, T, _ = x.shape
    h = rms(x, lw["ln1"], eps)
    q = einsum(mode, "btd,de->bte", h, lw["wq"])
    k = einsum(mode, "btd,de->bte", h, lw["wk"])
    v = einsum(mode, "btd,de->bte", h, lw["wv"])
    if c["attention_bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = q.reshape(B, T, -1, hd)
    k = k.reshape(B, T, -1, hd)
    v = v.reshape(B, T, -1, hd)
    if c["qk_norm"]:
        q, k = rms(q, lw["q_norm"], eps), rms(k, lw["k_norm"], eps)
    q, k = rope(q, pos, c["rope_theta"]), rope(k, pos, c["rope_theta"])
    a = attention(q, k, v, mode).reshape(B, T, -1)
    x = x + einsum(mode, "bte,ed->btd", a, lw["wo"])
    h = rms(x, lw["ln2"], eps)
    g = einsum(mode, "btd,df->btf", h, lw["w_gate"])
    u = einsum(mode, "btd,df->btf", h, lw["w_up"])
    return x + einsum(mode, "btf,fd->btd", jax.nn.silu(g) * u, lw["w_down"])


def hidden(w: Dict[str, Any], c: Dict[str, Any], tokens, mode="f32"):
    """Final-normed hidden states [B, T, d] for token ids [B, T]."""
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    x = jnp.take(w["embed"].astype(F32), tokens, axis=0)
    layers = {k: w[k] for k in w if k in STACKED}
    step = jax.checkpoint(functools.partial(_layer, c, mode))
    x, _ = jax.lax.scan(lambda x, lw: (step(x, lw, pos), None), x, layers)
    return rms(x, w["final_norm"].astype(F32), c["rms_norm_eps"])


def layer_matmul_params(c: Dict[str, Any]) -> int:
    """Weights of one decoder layer's matrix products."""
    d, hd, F = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * F


def matmul_params(c: Dict[str, Any]) -> int:
    """Matmul weights a token passes through: every decoder layer's and
    the LM head's."""
    return (c["num_hidden_layers"] * layer_matmul_params(c)
            + c["vocab_size"] * c["hidden_size"])


def attn_pair_flops(c: Dict[str, Any]) -> int:
    """FLOPs per (query, key) pair over all layers: QK^T and PV."""
    return (4 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"])


def cache_bytes_per_token(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V of every layer."""
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * dtype_bytes)


def param_bytes(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    d, L, hd = c["hidden_size"], c["num_hidden_layers"], c["head_dim"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    n = matmul_params(c) - c["vocab_size"] * d          # layers
    n += c["vocab_size"] * d * (1 if c["tie_word_embeddings"] else 2)
    n += L * 2 * d + d                                  # norms
    if c["attention_bias"]:
        n += L * (q + 2 * kv)
    if c["qk_norm"]:
        n += L * 2 * hd
    return n * dtype_bytes
