"""The DeepSeek-V3 decoder (Moonlight-16B-A3B's ``deepseek_v3``): what the
benchmark knows of its shape.

A configuration file names this module with ``"bench_arch":
"deepseek_moe"``. Two stacks of layers, each leaf stacked on a leading
axis: the ``first_k_dense_replace`` dense layers (leaves ``dense_*``) and
the expert layers after them.

    embed lm_head [V, d]   final_norm [d]
    per layer, dense_ prefixed in the dense stack:
      ln1 ln2 [L, d]   wq [L, d, H*(nope+rope)]   wkv_a [L, d, r+rope]
      kv_a_norm [L, r]   wkv_b [L, r, H*(nope+v)]   wo [L, H*v, d]
    dense stack:  dense_w_gate dense_w_up [Ld, d, F]   dense_w_down [Ld, F, d]
    expert stack: router [Lm, d, E]   e_bias [Lm, E]
                  w_gate w_up [Lm, Eh, d, f]   w_down [Lm, Eh, f, d]
                  shared_gate shared_up [Lm, d, S*f]   shared_down [Lm, S*f, d]

E is the router's width (``router_experts``); Eh the experts held here,
experts 0 .. Eh-1 (``n_routed_experts``, the chip's share); S the shared
experts. ``to_program`` / ``from_program`` rename these to and from the
program's parameter tree (repro.models.model.init_params), one stage per
stack.

``hidden`` is the float32 reference forward, written from the published
description (DeepSeek-V3 technical report, arXiv:2412.19437; Moonlight,
arXiv:2502.16982; the model's config.json), not from the program:
RMSNorm; multi-head latent attention without q LoRA, its kv latent
RMS-normed, RoPE on the rope part of q and on one k part shared by all
heads, scores over nope + rope dims scaled by 1/sqrt of that, v over its
own width; SwiGLU; the router's sigmoid scores, top-k chosen by scores +
e_bias, weighted by the chosen scores without the bias, normalised and
scaled by routed_scaling_factor. Attention runs in query blocks, each
checkpointed, so that its backward never holds all T x T probabilities.
Each held expert runs on every token, weighted by its routing weight there
(zero where it was not chosen), one expert at a time; the shared experts
are counted once. What the experts held elsewhere add is left out, as in
the program. RoPE rotates the halves of the rope part: with random weights
that is a permutation of the published interleaved pairs.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import F32, einsum, rms, rope

# config.json keys of this family -> the program's ArchConfig fields,
# beside spec.ARCH_FIELDS. n_routed_experts counts the experts held here;
# router_experts, the router's width, is the published n_routed_experts.
ARCH_FIELDS = {
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "moe_intermediate_size": "d_ff_expert",
    "router_experts": "n_experts",
    "n_routed_experts": "experts_held",
    "num_experts_per_tok": "top_k",
    "n_shared_experts": "n_shared_experts",
    "scoring_func": "router_score",
    "topk_method": "topk_method",
    "routed_scaling_factor": "routed_scaling",
    "first_k_dense_replace": "first_k_dense",
    "attention_bias": "qkv_bias",
}
# the configuration's keys that the reference reads
REF_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "intermediate_size", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
            "router_experts", "n_routed_experts", "num_experts_per_tok",
            "n_shared_experts", "scoring_func", "topk_method",
            "norm_topk_prob", "routed_scaling_factor",
            "first_k_dense_replace", "vocab_size", "rms_norm_eps",
            "rope_theta", "tie_word_embeddings", "param_dtype")
ATTN = ("wq", "wkv_a", "kv_a_norm", "wkv_b", "wo")
DENSE_FFN = ("w_gate", "w_up", "w_down")
EXPERTS = ("w_gate", "w_up", "w_down")
SHARED = {"shared_gate": "w_gate", "shared_up": "w_up",
          "shared_down": "w_down"}
LAYER = ("ln1", "ln2") + ATTN
STACKED = (tuple(f"dense_{k}" for k in LAYER + DENSE_FFN) + LAYER
           + ("router", "e_bias") + EXPERTS + tuple(SHARED))
# leaves AdamW decays: every matrix; not the norm gains nor the selection
# bias, which the published model updates by a rule of its own
MATRICES = tuple(k for k in ("embed", "lm_head") + STACKED
                 if not k.endswith(("ln1", "ln2", "kv_a_norm", "e_bias")))
# norm gains, made as 1 + N(0, 0.1^2)
NORMS = ("final_norm", "ln1", "ln2", "kv_a_norm", "dense_ln1", "dense_ln2",
         "dense_kv_a_norm")
# query rows per attention block: at T = 8192 the backward of one block
# holds ~2 GB of float32 scores and probabilities
ATTN_BLOCK = 128
# token rows per block of a SwiGLU: the dense layer's [B*T, 11264] float32
# intermediates would otherwise take ~0.7 GB each
FFN_ROWS = 2048
# the seeded selection bias's standard deviation: it changes the choice of
# tokens whose 6th and 7th scores lie within ~0.005 (at 0.05 a seed shifted
# the held experts' share of the load by ~20%, and the step's cost with
# it). It does not balance the load: nothing here updates the bias, and
# the router, trained from step 0, soon sends most tokens to one expert
E_BIAS_STD = 0.005


def _sizes(c):
    Ld = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    return Ld, c["num_hidden_layers"] - Ld


def shapes(c: Dict[str, Any]) -> Dict[str, tuple]:
    if c["q_lora_rank"] is not None or c["tie_word_embeddings"]:
        raise ValueError("deepseek_moe: q LoRA and tied embeddings are not "
                         "in this family's layout")
    if not c["norm_topk_prob"]:
        raise ValueError("deepseek_moe: the program always normalises the "
                         "chosen experts' weights (norm_topk_prob true)")
    d, V, H = c["hidden_size"], c["vocab_size"], c["num_attention_heads"]
    r, rope_d = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, vd = c["qk_nope_head_dim"], c["v_head_dim"]
    F, f = c["intermediate_size"], c["moe_intermediate_size"]
    E, Eh = c["router_experts"], c["n_routed_experts"]
    S = c["n_shared_experts"] * f
    Ld, Lm = _sizes(c)

    def layer(L):
        return {"ln1": (L, d), "ln2": (L, d), "wq": (L, d, H * (nope + rope_d)),
                "wkv_a": (L, d, r + rope_d), "kv_a_norm": (L, r),
                "wkv_b": (L, r, H * (nope + vd)), "wo": (L, H * vd, d)}

    s = {"embed": (V, d), "lm_head": (V, d), "final_norm": (d,)}
    if Ld:
        s.update({f"dense_{k}": v for k, v in layer(Ld).items()})
        s.update(dense_w_gate=(Ld, d, F), dense_w_up=(Ld, d, F),
                 dense_w_down=(Ld, F, d))
    if Lm:
        s.update(layer(Lm))
        s.update(router=(Lm, d, E), e_bias=(Lm, E), w_gate=(Lm, Eh, d, f),
                 w_up=(Lm, Eh, d, f), w_down=(Lm, Eh, f, d),
                 shared_gate=(Lm, d, S), shared_up=(Lm, d, S),
                 shared_down=(Lm, S, d))
    return s


def init_std(c: Dict[str, Any], name: str, shape) -> float:
    """Standard deviation of a leaf that is not a norm gain."""
    if name in ("embed", "lm_head"):
        return 0.02
    if name == "e_bias":
        return E_BIAS_STD
    std = 1.0 / math.sqrt(shape[-2])
    if name.endswith(("wo", "w_down", "shared_down")):
        std /= math.sqrt(2 * c["num_hidden_layers"])
    return std


def to_program(w: Dict[str, Any]) -> Dict[str, Any]:
    stages = []
    if "dense_ln1" in w:
        stages.append({"ln1": w["dense_ln1"], "ln2": w["dense_ln2"],
                       "attn": {k: w[f"dense_{k}"] for k in ATTN},
                       "mlp": {k: w[f"dense_{k}"] for k in DENSE_FFN}})
    if "ln1" in w:
        moe = {k: w[k] for k in ("router", "e_bias") + EXPERTS}
        moe["shared"] = {v: w[k] for k, v in SHARED.items()}
        stages.append({"ln1": w["ln1"], "ln2": w["ln2"],
                       "attn": {k: w[k] for k in ATTN}, "moe": moe})
    return {"embed": w["embed"], "lm_head": w["lm_head"],
            "final_norm": w["final_norm"], "stages": stages}


def from_program(p: Dict[str, Any]) -> Dict[str, Any]:
    w = {"embed": p["embed"], "lm_head": p["lm_head"],
         "final_norm": p["final_norm"]}
    for st in p["stages"]:
        if "mlp" in st:
            w.update({f"dense_{k}": v for k, v in
                      {"ln1": st["ln1"], "ln2": st["ln2"], **st["attn"],
                       **st["mlp"]}.items()})
        else:
            moe = dict(st["moe"])
            shared = moe.pop("shared")
            w.update(ln1=st["ln1"], ln2=st["ln2"], **st["attn"], **moe,
                     **{k: shared[v] for k, v in SHARED.items()})
    return w


def _attention(q, k, v, mode):
    """Causal attention, q [B, T, H, qd], k [B, T, H, qd], v [B, T, H, vd]
    -> [B, T, H, vd], scores scaled by 1/sqrt(qd); each query block is
    recomputed in the backward."""
    B, T, H, qd = q.shape
    blk = min(ATTN_BLOCK, T)
    nb = T // blk
    qb = q.reshape(B, nb, blk, H, qd).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one(args):
        qi, i = args
        s = einsum(mode, "bqhd,bkhd->bhqk", qi, k) / np.sqrt(qd)
        ok = jnp.arange(T)[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return einsum(mode, "bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one, (qb, jnp.arange(nb)))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, T, H, v.shape[-1])


def _mla(c, mode, x, lw, pos):
    B, T, _ = x.shape
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, vd = c["qk_nope_head_dim"], c["v_head_dim"]
    q = einsum(mode, "btd,de->bte", x, lw["wq"]).reshape(B, T, H, -1)
    kv_a = einsum(mode, "btd,de->bte", x, lw["wkv_a"])
    latent = rms(kv_a[..., :r], lw["kv_a_norm"], c["rms_norm_eps"])
    kv = einsum(mode, "btr,re->bte", latent, lw["wkv_b"]).reshape(
        B, T, H, nope + vd)
    theta = c["rope_theta"]
    k_rope = rope(kv_a[:, :, None, r:], pos, theta)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)],
                        -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (B, T, H, k_rope.shape[-1]))],
        -1)
    a = _attention(q, k, kv[..., nope:], mode).reshape(B, T, H * vd)
    return einsum(mode, "bte,ed->btd", a, lw["wo"])


def _swiglu(mode, h, gate, up, down):
    """SwiGLU over h [B, T, d], FFN_ROWS rows at a time, each block
    recomputed in the backward."""
    B, T, d = h.shape
    rows = h.reshape(-1, min(FFN_ROWS, B * T), d)

    @jax.checkpoint
    def one(x):
        g = einsum(mode, "nd,df->nf", x, gate)
        u = einsum(mode, "nd,df->nf", x, up)
        return einsum(mode, "nf,fd->nd", jax.nn.silu(g) * u, down)

    return jax.lax.map(one, rows).reshape(B, T, d)


def routing_weights(c, mode, h, router, e_bias):
    """[B, T, experts held]: each held expert's weight for each token,
    zero where the router did not choose it."""
    logits = einsum(mode, "btd,de->bte", h, router)
    if c["scoring_func"] == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + e_bias if c["topk_method"] == "noaux_tc" else scores
    _, top = jax.lax.top_k(choice, c["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, top, -1)
    weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    weight = weight * c["routed_scaling_factor"]
    held = jnp.arange(c["n_routed_experts"])
    return jnp.sum(weight[..., None] * (top[..., None] == held), axis=-2)


def _moe(c, mode, h, lw):
    gates = routing_weights(c, mode, h, lw["router"], lw["e_bias"])

    @jax.checkpoint
    def part(gate, up, down, g):
        return g[..., None] * _swiglu(mode, h, gate, up, down)

    def expert(y, args):
        # the sum keeps no residual, so the backward holds no carry
        return y + part(*args), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (lw["w_gate"], lw["w_up"], lw["w_down"],
                         jnp.moveaxis(gates, -1, 0)))
    return y + _swiglu(mode, h, lw["shared_gate"], lw["shared_up"],
                       lw["shared_down"])


def _layer(c, mode, dense, x, lw, pos):
    lw = {k: a.astype(F32) for k, a in lw.items()}
    eps = c["rms_norm_eps"]
    x = x + _mla(c, mode, rms(x, lw["ln1"], eps), lw, pos)
    h = rms(x, lw["ln2"], eps)
    if dense:
        return x + _swiglu(mode, h, lw["w_gate"], lw["w_up"], lw["w_down"])
    return x + _moe(c, mode, h, lw)


def hidden(w: Dict[str, Any], c: Dict[str, Any], tokens, mode="f32"):
    """Final-normed hidden states [B, T, d] for token ids [B, T]."""
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    x = jnp.take(w["embed"].astype(F32), tokens, axis=0)
    dense = {k[len("dense_"):]: w[k] for k in w if k.startswith("dense_")}
    moe = {k: w[k] for k in w if k in STACKED and not k.startswith("dense_")}
    for is_dense, layers in ((True, dense), (False, moe)):
        if layers:
            step = jax.checkpoint(functools.partial(_layer, c, mode,
                                                    is_dense))
            x, _ = jax.lax.scan(lambda x, lw: (step(x, lw, pos), None), x,
                                layers)
    return rms(x, w["final_norm"].astype(F32), c["rms_norm_eps"])


def _attn_params(c: Dict[str, Any]) -> int:
    d, H = c["hidden_size"], c["num_attention_heads"]
    r, rope_d = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, vd = c["qk_nope_head_dim"], c["v_head_dim"]
    return (d * H * (nope + rope_d) + d * (r + rope_d)
            + r * H * (nope + vd) + H * vd * d)


def matmul_params(c: Dict[str, Any]) -> int:
    """Matmul weights a token passes through: attention and the dense FFN,
    the router, the shared experts, on average top_k x Eh / E of the held
    experts, and the LM head."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    Ld, Lm = _sizes(c)
    expert = 3 * d * f
    active = (c["num_experts_per_tok"] * c["n_routed_experts"]
              / c["router_experts"])
    moe = (d * c["router_experts"] + c["n_shared_experts"] * expert
           + active * expert)
    return int((Ld + Lm) * _attn_params(c)
               + Ld * 3 * d * c["intermediate_size"] + Lm * moe
               + c["vocab_size"] * d)


def attn_pair_flops(c: Dict[str, Any]) -> int:
    """FLOPs per (query, key) pair over all layers: q.k over nope + rope
    dims and p.v over v's."""
    return (2 * c["num_hidden_layers"] * c["num_attention_heads"]
            * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
               + c["v_head_dim"]))


def cache_bytes_per_token(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """The latent and the shared rope part of k, every layer."""
    return (c["num_hidden_layers"]
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * dtype_bytes)


def param_bytes(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    return sum(int(np.prod(s)) for s in shapes(c).values()) * dtype_bytes


# the grouped products of the held experts, per training step: gate, up and
# down in the forward, again in the backward's recompute, and two products
# (one for the rows, one for the weights) for each in the backward; the
# program runs gate and up as one kernel, counted here as two
GMM_PRODUCTS = 12


def expert_gmm_flops(c: Dict[str, Any], rows: int) -> float:
    """Operations of one step's grouped products over ``rows`` routed rows
    (summed over the expert layers and the held experts)."""
    return (GMM_PRODUCTS * 2.0 * rows * c["hidden_size"]
            * c["moe_intermediate_size"])


def expert_gmm_bytes(c: Dict[str, Any], rows: int,
                     dtype_bytes: int = 2) -> float:
    """Bytes one step's grouped products move: each reads its rows and the
    held experts' weights of every expert layer once and writes its
    output."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    _, Lm = _sizes(c)
    weights = Lm * c["n_routed_experts"] * d * f
    return GMM_PRODUCTS * float(dtype_bytes) * (rows * (d + f) + weights)
