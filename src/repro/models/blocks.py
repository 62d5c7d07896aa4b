"""Block registry: init / forward / decode / cache-init per block kind.

Kinds: attn (attention+MLP), moe (attention+MoE), mamba2, mlstm, slstm,
plus the enc-dec decoder block (self-attn + cross-attn + MLP) used by
whisper, and the zamba2 shared attention block (ATTN kind, weights shared
across applications).

Full-sequence forwards return (x, aux, rows): aux is a scalar auxiliary
loss (MoE load balance; 0.0 elsewhere), so stages can be scanned uniformly,
and rows counts the routed rows each held expert computed (MoE blocks; None
elsewhere). MLA blocks have no cache: prefill, decode and cache init refuse
them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import (attn_decode, attn_decode_cross, attn_forward,
                        attn_prefill, cross_kv, init_attn_params,
                        init_kv_cache, no_latent_cache)
from .common import F32, rms_norm
from .mlp import init_mlp_params, init_moe_params, mlp_forward, moe_forward
from .ssm import (init_mamba2_cache, init_mamba2_params, mamba2_decode,
                  mamba2_forward)
from .xlstm import (init_mlstm_cache, init_mlstm_params, init_slstm_cache,
                    init_slstm_params, mlstm_decode, mlstm_forward,
                    slstm_decode, slstm_forward)

ZERO = jnp.zeros((), F32)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def init_block(kind: str, key, cfg, dtype, cross: bool = False):
    ks = jax.random.split(key, 4)
    if kind in ("attn", "moe"):
        p = {"ln1": jnp.ones((cfg.d_model,), dtype),
             "attn": init_attn_params(ks[0], cfg, dtype),
             "ln2": jnp.ones((cfg.d_model,), dtype)}
        if kind == "moe":
            p["moe"] = init_moe_params(ks[1], cfg, dtype)
        else:
            p["mlp"] = init_mlp_params(ks[1], cfg, dtype)
        if cross:  # enc-dec decoder block
            p["ln_x"] = jnp.ones((cfg.d_model,), dtype)
            p["xattn"] = init_attn_params(ks[2], cfg, dtype, cross=True)
        return p
    if kind == "mamba2":
        return {"ln1": jnp.ones((cfg.d_model,), dtype),
                "mixer": init_mamba2_params(ks[0], cfg, dtype)}
    if kind == "mlstm":
        return {"ln1": jnp.ones((cfg.d_model,), dtype),
                "mixer": init_mlstm_params(ks[0], cfg, dtype)}
    if kind == "slstm":
        return {"ln1": jnp.ones((cfg.d_model,), dtype),
                "mixer": init_slstm_params(ks[0], cfg, dtype)}
    raise ValueError(kind)


# --------------------------------------------------------------------------
# forward (full sequence)
# --------------------------------------------------------------------------
def _seq_constrain(cfg, y):
    """Sequence-parallel residual join: pin the mixer output to the
    seq-sharded layout BEFORE the residual add, so the TP partial-sum
    lowers to a reduce-scatter into the seq shard instead of a full
    all-reduce followed by a separate all-gather (Megatron-SP; ~2.4 TiB/step
    saved on nemotron-340b — EXPERIMENTS.md §Perf)."""
    if not cfg.seq_parallel or y.shape[1] <= 1:
        return y
    from repro.parallel import ctx as pctx
    dp = pctx.dp_axes_or_none()
    if dp is None:
        return y
    return pctx.constrain(y, dp, "model", None)


def block_forward(kind: str, p, cfg, x, *, pos, pos3=None, enc_out=None,
                  causal=True):
    if kind in ("attn", "moe"):
        a_out = attn_forward(p["attn"], cfg,
                             rms_norm(x, p["ln1"], cfg.norm_eps),
                             pos=pos, pos3=pos3, causal=causal,
                             use_rope=cfg.rope_theta > 0
                             or bool(cfg.mrope_sections))
        h = x + _seq_constrain(cfg, a_out)
        if "xattn" in p:
            h = h + attn_forward(p["xattn"], cfg,
                                 rms_norm(h, p["ln_x"], cfg.norm_eps),
                                 pos=pos, causal=False, kv_x=enc_out,
                                 use_rope=False)
        hn = rms_norm(h, p["ln2"], cfg.norm_eps)
        if kind == "moe":
            y, aux, rows = moe_forward(p["moe"], cfg, hn)
            return h + _seq_constrain(cfg, y), aux, rows
        return (h + _seq_constrain(cfg, mlp_forward(p["mlp"], cfg, hn)),
                ZERO, None)
    if kind == "mamba2":
        return x + mamba2_forward(p["mixer"], cfg,
                                  rms_norm(x, p["ln1"], cfg.norm_eps)), \
            ZERO, None
    if kind == "mlstm":
        return x + mlstm_forward(p["mixer"], cfg,
                                 rms_norm(x, p["ln1"], cfg.norm_eps)), \
            ZERO, None
    if kind == "slstm":
        return x + slstm_forward(p["mixer"], cfg,
                                 rms_norm(x, p["ln1"], cfg.norm_eps)), \
            ZERO, None
    raise ValueError(kind)


# --------------------------------------------------------------------------
# prefill (forward + build cache)
# --------------------------------------------------------------------------
def block_prefill(kind: str, p, cfg, x, *, pos, pos3=None, enc_out=None,
                  cache_size: int = 0):
    """Returns (x, cache). cache_size: KV slots to allocate (attention).

    Rolling (sliding-window) caches store position p at slot ``p % W`` so
    decode's ``cache_len % W`` write lands on the oldest entry.
    """
    if kind in ("attn", "moe"):
        if cfg.mla:
            no_latent_cache(cfg)
        hn = rms_norm(x, p["ln1"], cfg.norm_eps)
        a_out, (k, v) = attn_prefill(p["attn"], cfg, hn, pos=pos, pos3=pos3)
        h = x + a_out
        cache = {}
        T = x.shape[1]
        if cfg.sliding_window and cache_size:
            W = cache_size
            if T >= W:
                k, v = k[:, -W:], v[:, -W:]
                shift = T % W
                if shift:
                    k = jnp.roll(k, shift, axis=1)
                    v = jnp.roll(v, shift, axis=1)
            else:
                padw = W - T
                k = jnp.pad(k, ((0, 0), (0, padw), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, padw), (0, 0), (0, 0)))
        elif cache_size > T:
            # headroom for tokens generated after prefill (non-rolling)
            pad = cache_size - T
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cache["kv"] = (k, v)
        if "xattn" in p:
            h = h + attn_forward(p["xattn"], cfg,
                                 rms_norm(h, p["ln_x"], cfg.norm_eps),
                                 pos=pos, causal=False, kv_x=enc_out,
                                 use_rope=False)
            cache["xkv"] = cross_kv(p["xattn"], cfg, enc_out)
        hn2 = rms_norm(h, p["ln2"], cfg.norm_eps)
        if kind == "moe":
            y, _, _ = moe_forward(p["moe"], cfg, hn2)
            return h + y, cache
        return h + mlp_forward(p["mlp"], cfg, hn2), cache
    # recurrent kinds: run chunked forward capturing final state
    if kind == "mamba2":
        from .ssm import mamba2_dims
        # cheap route: run forward then replay decode state via scan_ref on
        # the *last* conv window only is incorrect; instead run the chunked
        # engine with state return. For prefill we re-run mixers statefully.
        y, cache = _recurrent_prefill_mamba2(p["mixer"], cfg,
                                             rms_norm(x, p["ln1"], cfg.norm_eps))
        return x + y, cache
    if kind == "mlstm":
        y, cache = _recurrent_prefill_mlstm(p["mixer"], cfg,
                                            rms_norm(x, p["ln1"], cfg.norm_eps))
        return x + y, cache
    if kind == "slstm":
        y, cache = _recurrent_prefill_slstm(p["mixer"], cfg,
                                            rms_norm(x, p["ln1"], cfg.norm_eps))
        return x + y, cache
    raise ValueError(kind)


def _recurrent_prefill_mamba2(p, cfg, x):
    """Forward + final (conv, ssm) state."""
    import repro.models.ssm as S
    Bsz, T, d = x.shape
    d_in, nheads, conv_dim = S.mamba2_dims(cfg)
    G, N = cfg.ssm_groups, cfg.ssm_state
    z, xc, Bc, Cc, dt = S._mamba2_proj(p, x)
    conv_in = jnp.concatenate([xc, Bc, Cc], axis=-1)
    conv_state = conv_in[:, -(cfg.ssm_conv - 1):]
    conv_out = jax.nn.silu(
        S.causal_conv1d(conv_in, p["conv_w"], p["conv_b"]).astype(F32)
    ).astype(x.dtype)
    xc, Bc, Cc = jnp.split(conv_out, [d_in, d_in + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    a = dt * A
    xh = xc.reshape(Bsz, T, nheads, cfg.ssm_head_dim)
    x_scaled = xh.astype(F32) * dt[..., None]
    Bm = Bc.reshape(Bsz, T, G, N)
    Cm = Cc.reshape(Bsz, T, G, N)
    chunk = min(256, T)
    if T % chunk:
        y, state = S.ssd_scan_ref(x_scaled, a, Bm, Cm)
    else:
        y, state = S.ssd_chunked(x_scaled, a, Bm, Cm, chunk)
    y = y + xh.astype(F32) * p["D"][None, None, :, None]
    y = y.reshape(Bsz, T, d_in).astype(x.dtype)
    y = S.rms_norm(y * jax.nn.silu(z.astype(F32)).astype(x.dtype), p["norm"],
                   cfg.norm_eps)
    # state ssm is [b, H, N, P] in engine layout; decode expects [b,H,N,P] too
    return S.matmul(y, p["out_proj"]), {"conv": conv_state, "ssm": state}


def _recurrent_prefill_mlstm(p, cfg, x):
    import repro.models.xlstm as X
    Bsz, T, _ = x.shape
    d_in, nh, dqk, dv = X.mlstm_dims(cfg)
    xb, z, q, k, v, i_log, f_log, _ = X._mlstm_qkvif(p, cfg, x)
    conv_state = xb[:, -(cfg.ssm_conv - 1):]
    ig = jnp.exp(i_log)
    v_in = v.astype(F32) * ig[..., None]
    chunk = T if T % 256 else 256
    y, n, state, nstate = X.ssd_chunked(v_in, f_log, k.astype(F32),
                                        q.astype(F32), chunk,
                                        norm_weights=ig)
    out = X._mlstm_output(p, cfg, y, n, z, Bsz, T)
    return out, {"conv": conv_state, "ssm": state, "ssm_n": nstate}


def _recurrent_prefill_slstm(p, cfg, x):
    import repro.models.xlstm as X
    Bsz, T, d = x.shape
    nh, dh = cfg.n_heads, d // cfg.n_heads
    wx = X.matmul(x, p["w_in"], out_dtype=F32)
    zeros = jnp.zeros((Bsz, d), F32)
    state0 = (zeros, zeros, jnp.full((Bsz, d), -jnp.inf, F32), zeros)

    def step(state, wx_t):
        new = X._slstm_cell(p, cfg, wx_t, state)
        return new, new[3]

    (c, n, m, h), hs = jax.lax.scan(step, state0, wx.transpose(1, 0, 2))
    hseq = hs.transpose(1, 0, 2)
    hn = X.group_norm_heads(hseq.reshape(Bsz, T, nh, dh),
                            p["gn"].astype(F32),
                            cfg.norm_eps).reshape(Bsz, T, d).astype(x.dtype)
    h2 = X.rms_norm(hn, p["ff_ln"], cfg.norm_eps)
    up = X.matmul(h2, p["ff_up"])
    gate = jax.nn.gelu(X.matmul(h2, p["ff_gate"]).astype(F32)).astype(x.dtype)
    out = hn + X.matmul(gate * up, p["ff_down"])
    return out, {"c": c, "n": n, "m": m, "h": h}


# --------------------------------------------------------------------------
# decode (one token, with cache)
# --------------------------------------------------------------------------
def block_decode(kind: str, p, cfg, x, cache, *, cache_len, rolling=False):
    if kind in ("attn", "moe"):
        if cfg.mla:
            no_latent_cache(cfg)
        hn = rms_norm(x, p["ln1"], cfg.norm_eps)
        a_out, kv = attn_decode(p["attn"], cfg, hn, cache["kv"],
                                cache_len=cache_len, rolling=rolling)
        h = x + a_out
        new_cache = dict(cache)
        new_cache["kv"] = kv
        if "xattn" in p:
            h = h + attn_decode_cross(
                p["xattn"], cfg, rms_norm(h, p["ln_x"], cfg.norm_eps),
                cache["xkv"])
        hn2 = rms_norm(h, p["ln2"], cfg.norm_eps)
        if kind == "moe":
            y, _, _ = moe_forward(p["moe"], cfg, hn2)
            return h + y, new_cache
        return h + mlp_forward(p["mlp"], cfg, hn2), new_cache
    if kind == "mamba2":
        y, c = mamba2_decode(p["mixer"], cfg,
                             rms_norm(x, p["ln1"], cfg.norm_eps), cache)
        return x + y, c
    if kind == "mlstm":
        y, c = mlstm_decode(p["mixer"], cfg,
                            rms_norm(x, p["ln1"], cfg.norm_eps), cache)
        return x + y, c
    if kind == "slstm":
        y, c = slstm_decode(p["mixer"], cfg,
                            rms_norm(x, p["ln1"], cfg.norm_eps), cache)
        return x + y, c
    raise ValueError(kind)


# --------------------------------------------------------------------------
# cache init (abstract-friendly: pure shape math)
# --------------------------------------------------------------------------
def init_block_cache(kind: str, cfg, batch: int, cache_size: int, dtype,
                     cross: bool = False, enc_len: int = 0):
    if kind in ("attn", "moe"):
        c = {"kv": init_kv_cache(cfg, batch, cache_size, dtype)}
        if cross:
            shape = (batch, enc_len, cfg.n_kv_heads, cfg.head_dim)
            c["xkv"] = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        return c
    if kind == "mamba2":
        return init_mamba2_cache(cfg, batch, dtype)
    if kind == "mlstm":
        return init_mlstm_cache(cfg, batch, dtype)
    if kind == "slstm":
        return init_slstm_cache(cfg, batch, dtype)
    raise ValueError(kind)
