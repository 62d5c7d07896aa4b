"""Model assembly: pattern -> stages, init, train forward, prefill, decode.

A model is a pytree of params + pure functions. The layer stack is grouped
into *stages* — maximal runs of identical block kind (cut additionally at
zamba2 shared-attention boundaries) — and each stage's params are stacked on
a leading axis and executed with ``lax.scan`` (small HLO, fast compile, remat
per block). Heterogeneous patterns (xLSTM 7:1, zamba2 every-6) become short
python sequences of scanned stages.

Supports: dense / MoE / SSM / hybrid LMs, enc-dec (whisper), VLM stub
frontend (patch embeddings merged into the token stream).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from .blocks import (ZERO, block_decode, block_forward, block_prefill,
                     init_block, init_block_cache)
from .common import (F32, dtype_of, embed_init, matmul, param_count_tree,
                     rms_norm, sinusoidal_positions)


# --------------------------------------------------------------------------
# stages
# --------------------------------------------------------------------------
def pattern_stages(cfg: ArchConfig) -> List[Tuple[str, int]]:
    """[(kind, count), ...] — runs of equal kind, cut at shared-attn bounds."""
    stages: List[Tuple[str, int]] = []
    for i, kind in enumerate(cfg.block_pattern):
        cut = (cfg.shared_attn_every
               and i % cfg.shared_attn_every == 0 and i > 0)
        if stages and stages[-1][0] == kind and not cut:
            stages[-1] = (kind, stages[-1][1] + 1)
        else:
            stages.append((kind, 1))
    return stages


def n_shared_applications(cfg: ArchConfig) -> int:
    """Shared attention applies once after every stage (stages are cut at
    multiples of shared_attn_every), so count = number of stages."""
    if not cfg.shared_attn_every:
        return 0
    return len(pattern_stages(cfg))


def _stack(trees: List[Any]):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def init_params(cfg: ArchConfig, key) -> Dict[str, Any]:
    dtype = dtype_of(cfg.param_dtype)
    keys = jax.random.fold_in(key, 0)
    p: Dict[str, Any] = {}
    p["embed"] = embed_init(jax.random.fold_in(keys, 1), cfg.vocab_size,
                            cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = embed_init(jax.random.fold_in(keys, 2),
                                  cfg.vocab_size, cfg.d_model, dtype)
    p["final_norm"] = jnp.ones((cfg.d_model,), dtype)

    cross = cfg.enc_dec
    stages = []
    li = 0
    for si, (kind, count) in enumerate(pattern_stages(cfg)):
        blocks = [init_block(kind, jax.random.fold_in(keys, 100 + li + j),
                             cfg, dtype, cross=cross)
                  for j in range(count)]
        li += count
        stages.append(_stack(blocks))
    p["stages"] = stages

    if cfg.shared_attn_every:
        p["shared"] = init_block("attn", jax.random.fold_in(keys, 7), cfg,
                                 dtype)
    if cfg.enc_dec:
        enc_blocks = [init_block("attn",
                                 jax.random.fold_in(keys, 5000 + j), cfg,
                                 dtype)
                      for j in range(cfg.n_enc_layers)]
        p["encoder"] = _stack(enc_blocks)
        p["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
    return p


def abstract_params(cfg: ArchConfig):
    """ShapeDtypeStruct tree — no allocation (dry-run / sharding planning)."""
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))


def param_count(cfg: ArchConfig) -> int:
    return param_count_tree(abstract_params(cfg))


# --------------------------------------------------------------------------
# stage runners
# --------------------------------------------------------------------------
def _remat_wrap(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)   # full


def run_stage(kind, stage_params, cfg, x, *, pos, pos3=None, enc_out=None,
              causal=True):
    """Scan the stacked blocks of one stage. Returns (x, aux_sum, rows):
    rows [blocks, experts held] for an MoE stage, else None."""
    from repro.parallel import ctx as pctx

    def body(x, layer_p):
        if cfg.seq_parallel:
            # sequence-parallel residual stream: the remat'd block-boundary
            # activation is stored seq-sharded over the TP axis (fits HBM
            # for the 340B config; see DESIGN.md §5).
            dp = pctx.dp_axes_or_none()
            if dp is not None and x.shape[1] > 1:
                x = pctx.constrain(x, dp, "model", None)
        return block_forward(kind, layer_p, cfg, x, pos=pos, pos3=pos3,
                             enc_out=enc_out, causal=causal)
    body = _remat_wrap(body, cfg)

    def scan_fn(carry, layer_p):
        x, aux = carry
        x2, a, rows = body(x, layer_p)
        return (x2, aux + a), rows

    (x, aux), rows = jax.lax.scan(scan_fn, (x, ZERO), stage_params)
    return x, aux, rows


def run_stage_prefill(kind, stage_params, cfg, x, *, pos, pos3=None,
                      enc_out=None, cache_size=0):
    def scan_fn(x, layer_p):
        x2, cache = block_prefill(kind, layer_p, cfg, x, pos=pos, pos3=pos3,
                                  enc_out=enc_out, cache_size=cache_size)
        return x2, cache

    x, caches = jax.lax.scan(scan_fn, x, stage_params)
    return x, caches


def run_stage_decode(kind, stage_params, cfg, x, caches, *, cache_len,
                     rolling=False):
    def scan_fn(x, inp):
        layer_p, cache = inp
        x2, c2 = block_decode(kind, layer_p, cfg, x, cache,
                              cache_len=cache_len, rolling=rolling)
        return x2, c2

    x, new_caches = jax.lax.scan(scan_fn, x, (stage_params, caches))
    return x, new_caches


# --------------------------------------------------------------------------
# embedding / head
# --------------------------------------------------------------------------
def embed_tokens(p, cfg, tokens, patch_embeds=None, patch_pos=None):
    h = jnp.take(p["embed"], tokens, axis=0)
    if patch_embeds is not None:
        # VLM stub frontend: precomputed patch embeddings scattered into the
        # token stream at patch_pos (per-batch positions).
        b_idx = jnp.arange(h.shape[0])[:, None]
        h = h.at[b_idx, patch_pos].set(patch_embeds.astype(h.dtype))
    if cfg.rope_theta == 0 and not cfg.mrope_sections:
        # absolute sinusoidal positions (whisper)
        T = h.shape[1]
        h = h + sinusoidal_positions(T, cfg.d_model).astype(h.dtype)[None]
    return h


def lm_logits(p, cfg, h):
    w = p["embed"].T if cfg.tie_embeddings else p["lm_head"].T
    return matmul(rms_norm(h, p["final_norm"], cfg.norm_eps), w,
                  out_dtype=jnp.bfloat16)


# --------------------------------------------------------------------------
# encoder (whisper)
# --------------------------------------------------------------------------
def encode(p, cfg, frames):
    """frames: [B, S_enc, d] stub embeddings -> encoder output."""
    h = frames + sinusoidal_positions(frames.shape[1],
                                      cfg.d_model).astype(frames.dtype)[None]
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1])[None],
                           frames.shape[:2])

    def body(x, layer_p):
        return block_forward("attn", layer_p, cfg, x, pos=pos,
                             causal=False)[0]
    body = _remat_wrap(body, cfg)

    def scan_fn(x, layer_p):
        return body(x, layer_p), None
    h, _ = jax.lax.scan(scan_fn, h, p["encoder"])
    return rms_norm(h, p["enc_norm"], cfg.norm_eps)


# --------------------------------------------------------------------------
# full forward (training)
# --------------------------------------------------------------------------
def forward_hidden(p, cfg, tokens, **kw):
    """Final hidden states [B, T, d] (before the final norm) and the
    auxiliary loss."""
    h, aux, _ = forward_with_rows(p, cfg, tokens, **kw)
    return h, aux


def forward_with_rows(p, cfg, tokens, *, pos=None, pos3=None, enc_out=None,
                      patch_embeds=None, patch_pos=None):
    """forward_hidden's (h, aux) and the routed rows of each held expert
    of each MoE block, [MoE blocks, experts held] int32 (None without
    experts)."""
    B, T = tokens.shape
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    h = embed_tokens(p, cfg, tokens, patch_embeds, patch_pos)
    aux, rows = ZERO, []
    stages = pattern_stages(cfg)
    for si, (kind, _) in enumerate(stages):
        h, a, r = run_stage(kind, p["stages"][si], cfg, h, pos=pos,
                            pos3=pos3, enc_out=enc_out)
        aux = aux + a
        if r is not None:
            rows.append(r)
        if cfg.shared_attn_every:
            h, a2, _ = block_forward("attn", p["shared"], cfg, h, pos=pos)
            aux = aux + a2
    return h, aux, jnp.concatenate(rows) if rows else None


def forward_loss(p, cfg, batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """batch: {tokens [B,T], labels [B,T] (-1 = ignore), + modality extras}.

    Returns (loss, metrics); with experts, metrics["expert_rows"] holds
    the rows each held expert of each MoE block computed.
    """
    tokens = batch["tokens"]
    labels = batch["labels"]
    enc_out = None
    if cfg.enc_dec:
        enc_out = encode(p, cfg, batch["frames"])
    h, aux, rows = forward_with_rows(
        p, cfg, tokens,
        pos3=batch.get("pos3"),
        enc_out=enc_out,
        patch_embeds=batch.get("patch_embeds"),
        patch_pos=batch.get("patch_pos"))
    logits = lm_logits(p, cfg, h)                       # [B, T, V] bf16
    # next-token shift
    logits = logits[:, :-1]
    targets = labels[:, 1:]
    mask = (targets >= 0).astype(F32)
    lse = jax.nn.logsumexp(logits.astype(F32), axis=-1)
    tgt = jnp.take_along_axis(
        logits.astype(F32),
        jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    nll = (lse - tgt) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll) / denom
    metrics = {"nll": loss, "aux": aux,
               "ntokens": jnp.sum(mask)}
    if rows is not None:
        metrics["expert_rows"] = rows
    return loss + aux, metrics


def _sinusoid_at(pos, d: int):
    """Sinusoidal position rows at (scalar or [B]) positions -> [..., d]."""
    import math as _m
    half = d // 2
    log_timescale = _m.log(10000.0) / max(half - 1, 1)
    inv = jnp.exp(-log_timescale * jnp.arange(half, dtype=F32))
    p = jnp.asarray(pos, F32)
    scaled = p[..., None] * inv
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=-1)


# --------------------------------------------------------------------------
# serving: prefill + decode
# --------------------------------------------------------------------------
def kv_cache_size(cfg, seq_len: int) -> int:
    if cfg.sliding_window:
        return cfg.sliding_window     # rolling: slot = pos % window
    return seq_len


def init_cache(cfg: ArchConfig, batch: int, seq_len: int, dtype=None):
    """Abstract-friendly cache allocation for every stage (+ shared/cross)."""
    dtype = dtype or dtype_of(cfg.param_dtype)
    size = kv_cache_size(cfg, seq_len)
    caches = []
    for kind, count in pattern_stages(cfg):
        one = init_block_cache(kind, cfg, batch, size, dtype,
                               cross=cfg.enc_dec, enc_len=cfg.enc_len)
        caches.append(jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (count,) + x.shape), one))
    cache: Dict[str, Any] = {"stages": caches}
    if cfg.shared_attn_every:
        napp = n_shared_applications(cfg)
        one = init_block_cache("attn", cfg, batch, size, dtype)
        cache["shared"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (napp,) + x.shape), one)
    return cache


def prefill(p, cfg, tokens, *, pos3=None, frames=None, patch_embeds=None,
            patch_pos=None, pad: int = 64):
    """Process the prompt; returns (last-position logits, cache).

    ``pad`` — extra KV slots reserved for tokens generated after prefill
    (ignored for rolling sliding-window caches).
    """
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    enc_out = encode(p, cfg, frames) if cfg.enc_dec else None
    h = embed_tokens(p, cfg, tokens, patch_embeds, patch_pos)
    size = kv_cache_size(cfg, T)
    if not cfg.sliding_window:  # non-rolling: add generation headroom
        size = T + pad
    caches = []
    shared_caches = []
    for si, (kind, _) in enumerate(pattern_stages(cfg)):
        h, c = run_stage_prefill(kind, p["stages"][si], cfg, h, pos=pos,
                                 pos3=pos3, enc_out=enc_out, cache_size=size)
        caches.append(c)
        if cfg.shared_attn_every:
            h, sc = block_prefill("attn", p["shared"], cfg, h, pos=pos,
                                  cache_size=size)
            shared_caches.append(sc)
    cache: Dict[str, Any] = {"stages": caches}
    if cfg.shared_attn_every:
        cache["shared"] = _stack(shared_caches)
    logits = lm_logits(p, cfg, h[:, -1:])
    return logits[:, 0], cache


def decode_step(p, cfg, token, cache, cache_len):
    """One token for every sequence. token: [B] int32; cache_len: scalar.

    Returns (logits [B, V], new_cache).
    """
    B = token.shape[0]
    rolling = cfg.sliding_window > 0
    h = jnp.take(p["embed"], token[:, None], axis=0)
    if cfg.rope_theta == 0 and not cfg.mrope_sections:
        # absolute sinusoid at the current position (whisper decode)
        cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
        h = h + _sinusoid_at(cl, cfg.d_model).astype(h.dtype)[:, None]
    new_stage_caches = []
    shared_new = []
    for si, (kind, _) in enumerate(pattern_stages(cfg)):
        h, c = run_stage_decode(kind, p["stages"][si], cfg, h,
                                cache["stages"][si], cache_len=cache_len,
                                rolling=rolling)
        new_stage_caches.append(c)
        if cfg.shared_attn_every:
            app_idx = len(shared_new)
            sc = jax.tree_util.tree_map(lambda x: x[app_idx],
                                        cache["shared"])
            h, sc2 = block_decode("attn", p["shared"], cfg, h, sc,
                                  cache_len=cache_len, rolling=rolling)
            shared_new.append(sc2)
    new_cache: Dict[str, Any] = {"stages": new_stage_caches}
    if cfg.shared_attn_every:
        new_cache["shared"] = _stack(shared_new)
    logits = lm_logits(p, cfg, h)
    return logits[:, 0], new_cache
