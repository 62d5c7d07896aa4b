"""Attention: GQA with qk-norm / biases / RoPE / M-RoPE / sliding window,
and multi-head latent attention (MLA, DeepSeek-V2/V3).

Three implementations share one math definition (``ref`` oracle):
  * ``naive``   — materializes [T, S] scores (smoke tests, tiny shapes)
  * ``chunked`` — lax.map over query blocks with online softmax; flash-
                  attention memory profile in pure jnp. Default for training
                  and prefill (portable; honest HLO bytes for the roofline).
  * ``pallas``  — repro.kernels.flash_attention, compiled for the TPU.
                  Selected via cfg.attn_impl == "pallas"; any other backend
                  raises rather than running the Pallas interpreter.

Decode attends one new token against a (possibly rolling) KV cache. MLA
runs in training and full forwards only: it has no latent cache yet, so
prefill and decode refuse it (``no_latent_cache``).

v may be narrower than q and k (MLA: q.k over 192 dims, v over 128); scores
are scaled by q's width and the output has v's.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .common import (F32, apply_mrope, apply_rope, dense_init, matmul,
                     rms_norm, zeros)

NEG_INF = -1e30
# attend_chunked checkpoints each query block when the probabilities of all
# blocks, which its backward would otherwise keep, exceed this many bytes
SAVED_PROBS_BYTES = 1 << 30


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------
def init_attn_params(key, cfg, dtype, cross: bool = False):
    if cfg.mla and not cross:
        return init_mla_params(key, cfg, dtype)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, qd, dtype),
        "wk": dense_init(ks[1], d, kvd, dtype),
        "wv": dense_init(ks[2], d, kvd, dtype),
        "wo": dense_init(ks[3], qd, d, dtype, scale=1.0 / math.sqrt(2 * max(cfg.n_layers, 1))),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros((qd,), dtype)
        p["bk"] = zeros((kvd,), dtype)
        p["bv"] = zeros((kvd,), dtype)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
    return p


def init_mla_params(key, cfg, dtype):
    """q straight from x (no q LoRA); k and v from a normed latent of
    ``kv_lora_rank`` made beside the shared RoPE part of k (``wkv_a``)."""
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, H * (nope + rope), dtype),
        "wkv_a": dense_init(ks[1], d, r + rope, dtype),
        "kv_a_norm": jnp.ones((r,), dtype),
        "wkv_b": dense_init(ks[2], r, H * (nope + vd), dtype),
        "wo": dense_init(ks[3], H * vd, d, dtype,
                         scale=1.0 / math.sqrt(2 * max(cfg.n_layers, 1))),
    }


def no_latent_cache(cfg):
    """Prefill and decode of an MLA config: refused, not approximated."""
    raise NotImplementedError(
        f"{cfg.name}: multi-head latent attention has no latent cache "
        f"(kv_lora_rank {cfg.kv_lora_rank} + rope {cfg.qk_rope_head_dim} a "
        f"token) to prefill or decode through; it trains and runs full "
        f"forwards only")


# --------------------------------------------------------------------------
# core math
# --------------------------------------------------------------------------
def _project_qkv(p, cfg, x, kv_x=None):
    """x: [B, T, d] -> q [B,T,H,hd], k/v [B,S,KV,hd]."""
    kv_x = x if kv_x is None else kv_x
    q = matmul(x, p["wq"])
    k = matmul(kv_x, p["wk"])
    v = matmul(kv_x, p["wv"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    B, T = x.shape[:2]
    S = kv_x.shape[1]
    q = q.reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _rope_qk(q, k, cfg, pos, pos3=None):
    if cfg.mrope_sections:
        assert pos3 is not None, "M-RoPE arch requires pos3 [3,B,T]"
        q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.rope_theta > 0:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    return q, k


def _expand_kv(k, n_heads: int):
    """[B, S, KV, hd] -> [B, S, H, hd] by group repetition."""
    B, S, KV, hd = k.shape
    rep = n_heads // KV
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


def mask_bias(q_pos, k_pos, causal: bool, window: int):
    """Additive mask [Tq, Sk] from absolute positions."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok = ok & (k_pos[None, :] <= q_pos[:, None])
    if window > 0:
        ok = ok & (k_pos[None, :] > q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(F32)


def attend_naive(q, k, v, *, causal: bool, window: int, q_offset: int = 0):
    """q/k: [B,T,H,hd], [B,S,KV,hd]; v: [B,S,KV,vd] -> [B,T,H,vd].
    Materializes scores."""
    B, T, H, hd = q.shape
    S = k.shape[1]
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bthd,bshd->bhts", q, k,
                        preferred_element_type=F32) * scale
    q_pos = jnp.arange(T) + q_offset
    k_pos = jnp.arange(S)
    scores = scores + mask_bias(q_pos, k_pos, causal, window)[None, None]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v,
                     preferred_element_type=F32)
    return out.astype(q.dtype)


def _softmax_apart(scores):
    """Softmax over the last axis with the row max in a fusion of its own.
    Fused with the subtraction, XLA's TPU compiler may take the max of a
    long row as a reduce-window over the whole row for every element
    (O(S^2) a row): 47 ms for one [2, 16, 512, 8192] block of latent
    attention on a v5e, against ~1 ms to read it."""
    m = jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(scores, -1, keepdims=True)))
    e = jnp.exp(scores - m)
    return e / jnp.sum(e, -1, keepdims=True)


def attend_chunked(q, k, v, *, causal: bool, window: int, q_offset: int = 0,
                   q_block: int = 512):
    """Flash-style: map over query blocks, online-softmax over KV.

    Memory O(q_block * S) instead of O(T * S). Pure jnp; the Pallas kernel in
    repro.kernels.flash_attention is the TPU-tiled version of this schedule.
    Where the backward would keep more than SAVED_PROBS_BYTES of
    probabilities, each block is recomputed in the backward instead. The
    softmax takes the row max apart (``_softmax_apart``).
    """
    B, T, H, hd = q.shape
    S = k.shape[1]
    if T % q_block != 0:
        return attend_naive(q, k, v, causal=causal, window=window,
                            q_offset=q_offset)
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scale = 1.0 / math.sqrt(hd)
    n_blocks = T // q_block
    qb = q.reshape(B, n_blocks, q_block, H, hd).transpose(1, 0, 2, 3, 4)
    k_pos = jnp.arange(S)

    def one_block(args):
        qi, i = args
        q_pos = i * q_block + jnp.arange(q_block) + q_offset
        scores = jnp.einsum("bthd,bshd->bhts", qi, k,
                            preferred_element_type=F32) * scale
        ok = jnp.ones((q_block, S), bool)
        if causal:
            ok = ok & (k_pos[None, :] <= q_pos[:, None])
        if window > 0:
            ok = ok & (k_pos[None, :] > q_pos[:, None] - window)
        scores = jnp.where(ok[None, None], scores, NEG_INF)
        probs = _softmax_apart(scores)
        return jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v,
                          preferred_element_type=F32).astype(q.dtype)

    if B * H * T * S * 4 > SAVED_PROBS_BYTES:
        one_block = jax.checkpoint(one_block)
    out = jax.lax.map(one_block, (qb, jnp.arange(n_blocks)))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, T, H, v.shape[-1])


def attend_scan_kv(q, k, v, *, causal: bool, window: int, q_offset: int = 0,
                   kv_block: int = 512):
    """Flash-style online softmax scanning KV blocks (carry = whole Q).

    The distribution-friendly variant for CONTEXT PARALLELISM: the carry
    (acc, m, l) inherits q's sequence sharding, while the scanned KV blocks
    stay replicated — every device streams the full KV through its local
    sequence shard. Memory O(T_local * kv_block).
    """
    B, T, H, hd = q.shape
    S = k.shape[1]
    if S % kv_block != 0:
        return attend_chunked(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scale = 1.0 / math.sqrt(hd)
    nb = S // kv_block
    kb = k.reshape(B, nb, kv_block, H, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, kv_block, H, -1).transpose(1, 0, 2, 3, 4)
    q32 = q.astype(F32)
    q_pos = (jnp.arange(T) + q_offset)[:, None]

    def step(carry, inp):
        acc, m, l = carry                       # [B,H,T,hd], [B,H,T], [B,H,T]
        kj, vj, j = inp
        s = jnp.einsum("bthd,bshd->bhts", q32, kj.astype(F32)) * scale
        k_pos = (j * kv_block + jnp.arange(kv_block))[None, :]
        ok = jnp.ones((T, kv_block), bool)
        if causal:
            ok = ok & (k_pos <= q_pos)
        if window > 0:
            ok = ok & (k_pos > q_pos - window)
        s = jnp.where(ok[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhts,bshd->bhtd", p, vj.astype(F32))
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, H, T, v.shape[-1]), F32)
    m0 = jnp.full((B, H, T), NEG_INF, F32)
    l0 = jnp.zeros((B, H, T), F32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0),
                                  (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attend_context_parallel(q, k, v, cfg, mesh, *, causal: bool,
                            window: int):
    """Context-parallel attention as an EXPLICIT shard_map over 'model'.

    q is sequence-sharded; k/v are replicated over 'model', so the forward
    is collective-free (each device streams the full KV through its local
    query shard) and autodiff reduces dk/dv with ONE psum per call at the
    shard_map boundary — where the GSPMD-auto formulation reinserted the
    partial-sum INSIDE the KV-block scan (8 psums of [B,H,blk,hd] per layer
    per microbatch; −187 GiB/step on qwen3-14b — EXPERIMENTS.md §Perf)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.parallel import ctx as pctx
    T = q.shape[1]
    tp = mesh.shape["model"]
    dp = pctx.plan_or_none().dp

    def local(q_l, k_l, v_l):
        idx = jax.lax.axis_index("model")
        off = idx * (T // tp)
        return attend_scan_kv(q_l, k_l, v_l, causal=causal, window=window,
                              q_offset=off)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(dp, "model", None, None),
                             P(dp, None, None, None),
                             P(dp, None, None, None)),
                   out_specs=P(dp, "model", None, None),
                   check_vma=False)
    return fn(q, k, v)


def attend(q, k, v, cfg, *, causal: bool = True, q_offset: int = 0,
           impl: Optional[str] = None):
    impl = impl or cfg.attn_impl
    window = cfg.sliding_window
    from repro.parallel import ctx as pctx
    plan = pctx.plan_or_none()
    if plan is not None and plan.context_parallel and q.shape[1] > 1:
        dp = plan.dp
        q = pctx.constrain(q, dp, "model", None, None)
        k = pctx.constrain(k, dp, None, None, None)
        v = pctx.constrain(v, dp, None, None, None)
        mesh = pctx.mesh_or_none()
        if (cfg.cp_shard_map and mesh is not None and q_offset == 0
                and q.shape[1] % mesh.shape["model"] == 0):
            out = attend_context_parallel(q, k, v, cfg, mesh,
                                          causal=causal, window=window)
        else:
            out = attend_scan_kv(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset)
        return pctx.constrain(out, dp, "model", None, None)
    if impl == "pallas":
        if v.shape[-1] != q.shape[-1]:
            raise ValueError("attn_impl='pallas' needs v as wide as q; "
                             "use 'chunked'")
        if jax.default_backend() != "tpu":
            raise RuntimeError(
                f"attn_impl='pallas' compiles for the TPU; the backend is "
                f"{jax.default_backend()!r}")
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset)
    if impl == "chunked":
        return attend_chunked(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
    return attend_naive(q, k, v, causal=causal, window=window,
                        q_offset=q_offset)


# --------------------------------------------------------------------------
# block-level entry points
# --------------------------------------------------------------------------
def attn_forward(p, cfg, x, *, pos, pos3=None, causal=True, kv_x=None,
                 use_rope=True):
    """Full-sequence attention (training / encoder). Returns [B, T, d]."""
    if "wkv_a" in p:
        return mla_forward(p, cfg, x, pos=pos)
    q, k, v = _project_qkv(p, cfg, x, kv_x)
    if use_rope:
        q, k = _rope_qk(q, k, cfg, pos, pos3)
    out = attend(q, k, v, cfg, causal=causal)
    B, T = x.shape[:2]
    return matmul(out.reshape(B, T, cfg.q_dim), p["wo"])


def mla_forward(p, cfg, x, *, pos):
    """Multi-head latent attention over the full sequence -> [B, T, d].

    RoPE rotates only the ``qk_rope_head_dim`` part of q and k; k's part
    comes from x once and is shared by every head. q.k runs over nope +
    rope dims (scaled by 1/sqrt of that), v over ``v_head_dim``.
    """
    B, T, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    q = matmul(x, p["wq"]).reshape(B, T, H, -1)
    kv_a = matmul(x, p["wkv_a"])
    latent = rms_norm(kv_a[..., :r], p["kv_a_norm"], cfg.norm_eps)
    kv = matmul(latent, p["wkv_b"]).reshape(B, T, H, nope + vd)
    k_rope = apply_rope(kv_a[:, :, None, r:], pos, cfg.rope_theta)
    q = jnp.concatenate(
        [q[..., :nope], apply_rope(q[..., nope:], pos, cfg.rope_theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope, (B, T, H, k_rope.shape[-1]))], -1)
    out = attend(q, k, kv[..., nope:], cfg, causal=True)
    return matmul(out.reshape(B, T, H * vd), p["wo"])


def attn_prefill(p, cfg, x, *, pos, pos3=None):
    """Training-style pass that also returns the KV cache (k, v)."""
    if cfg.mla:
        no_latent_cache(cfg)
    q, k, v = _project_qkv(p, cfg, x)
    q, k = _rope_qk(q, k, cfg, pos, pos3)
    out = attend(q, k, v, cfg, causal=True)
    B, T = x.shape[:2]
    return matmul(out.reshape(B, T, cfg.q_dim), p["wo"]), (k, v)


def attn_decode(p, cfg, x, cache, *, cache_len, pos3=None, rolling=False):
    """One-token decode. x: [B, 1, d]; cache: (k, v) [B, S, KV, hd].

    ``cache_len`` — number of valid positions already in the cache; a scalar
    or a per-sequence [B] vector (continuous batching). The new token is
    written at ``cache_len % S`` when ``rolling`` (sliding window) else at
    ``cache_len``. Returns (out [B,1,d], new_cache).
    """
    if cfg.mla:
        no_latent_cache(cfg)
    from repro.parallel import ctx as pctx
    plan = pctx.plan_or_none()
    k_cache, v_cache = cache
    B, S = k_cache.shape[0], k_cache.shape[1]
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    q, k_new, v_new = _project_qkv(p, cfg, x)
    # When the cache is SEQUENCE-sharded over the model axis (kv-heads don't
    # divide it), the GSPMD solver otherwise reshards the whole cache to
    # head sharding every step (involuntary full rematerialization, ~52
    # GiB/token wire at 0.6B scale — EXPERIMENTS.md §Perf iteration 2).
    # Pinning the cache/scores to SEQ sharding turns decode attention into
    # GSPMD-mediated flash-decoding: each device scores its local KV shard,
    # and the softmax/value contractions reduce with tiny [B,H] collectives.
    seq_shard = (plan is not None and not plan.tp_kv_heads
                 and cfg.decode_gather_q)
    dp = plan.dp if plan is not None else None
    if seq_shard:
        q = pctx.constrain(q, dp, None, None, None)
        k_new = pctx.constrain(k_new, dp, None, None, None)
        v_new = pctx.constrain(v_new, dp, None, None, None)
    pos = cl[:, None]
    if cfg.mrope_sections:
        p3 = pos3 if pos3 is not None else jnp.broadcast_to(
            pos[None], (3, B, 1))
        q, k_new = _rope_qk(q, k_new, cfg, pos, p3)
    else:
        q, k_new = _rope_qk(q, k_new, cfg, pos)
    slot = (cl % S) if rolling else jnp.minimum(cl, S - 1)
    if jnp.ndim(cache_len) == 0:
        # all sequences write the SAME slot (SPMD serving path): a
        # dynamic-update-slice on the seq dim. GSPMD partitions DUS on a
        # sharded dim as a masked LOCAL update; the general per-row scatter
        # below is expanded by GSPMD into a full-cache f32 select chain
        # (~300 GB/token at 0.6B scale — EXPERIMENTS.md §Perf iteration 4).
        s0 = (cache_len % S) if rolling else jnp.minimum(cache_len, S - 1)
        zero = jnp.zeros((), s0.dtype) if hasattr(s0, "dtype") else 0
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_new.astype(k_cache.dtype), (zero, s0, zero, zero))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_new.astype(v_cache.dtype), (zero, s0, zero, zero))
    else:
        # continuous batching: per-sequence cache lengths -> row scatter
        b_idx = jnp.arange(B)
        k_cache = k_cache.at[b_idx, slot].set(
            k_new[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[b_idx, slot].set(
            v_new[:, 0].astype(v_cache.dtype))
    if seq_shard:
        k_cache = pctx.constrain(k_cache, dp, "model", None, None)
        v_cache = pctx.constrain(v_cache, dp, "model", None, None)

    scale = 1.0 / math.sqrt(cfg.head_dim)
    idx = jnp.arange(S)
    if rolling:
        # slots written so far = min(cache_len + 1, S) (slot p%S for pos p)
        valid = idx[None, :] <= jnp.minimum(cl, S - 1)[:, None]
    else:
        valid = idx[None, :] <= cl[:, None]

    if cfg.decode_grouped_attn:
        # grouped-query attention without materializing head-repeated KV:
        # q [B,1,H,hd] -> [B,KV,G,hd]; contract straight against the cache
        KV = cfg.n_kv_heads
        G = cfg.n_heads // KV
        qg = q[:, 0].reshape(B, KV, G, cfg.head_dim)
        scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                            preferred_element_type=F32) * scale
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        if seq_shard:
            scores = pctx.constrain(scores, dp, None, None, "model")
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(v_cache.dtype),
                         v_cache,
                         preferred_element_type=F32).astype(x.dtype)
        if seq_shard:
            out = pctx.constrain(out, dp, None, None, None)
        out = out.reshape(B, 1, cfg.q_dim)
    else:
        kk = _expand_kv(k_cache, cfg.n_heads)
        vv = _expand_kv(v_cache, cfg.n_heads)
        if seq_shard:
            kk = pctx.constrain(kk, dp, "model", None, None)
            vv = pctx.constrain(vv, dp, "model", None, None)
        scores = jnp.einsum("bthd,bshd->bhts", q, kk,
                            preferred_element_type=F32) * scale
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        if seq_shard:
            # scores stay sharded on the KV-sequence dim; softmax over the
            # sharded axis lowers to local max/sum + small cross-shard
            # reduces
            scores = pctx.constrain(scores, dp, None, None, "model")
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhts,bshd->bthd", probs.astype(vv.dtype), vv,
                         preferred_element_type=F32).astype(x.dtype)
        if seq_shard:
            out = pctx.constrain(out, dp, None, None, None)
        out = out.reshape(B, 1, cfg.q_dim)
    out = matmul(out, p["wo"])
    return out, (k_cache, v_cache)


def attn_decode_cross(p, cfg, x, enc_kv):
    """Cross-attention for enc-dec decode: precomputed encoder (k, v)."""
    B = x.shape[0]
    q = matmul(x, p["wq"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
    q = q.reshape(B, 1, cfg.n_heads, cfg.head_dim)
    k, v = enc_kv
    out = attend_naive(q, k, v, causal=False, window=0)
    return matmul(out.reshape(B, 1, cfg.q_dim), p["wo"])


def cross_kv(p, cfg, enc_out):
    """Precompute cross-attention k, v from encoder output."""
    B, S = enc_out.shape[:2]
    k = matmul(enc_out, p["wk"])
    v = matmul(enc_out, p["wv"])
    if "bk" in p:
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return (k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim),
            v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim))


def init_kv_cache(cfg, batch: int, max_seq: int, dtype):
    if cfg.mla:
        no_latent_cache(cfg)
    shape = (batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
