"""Per-arch reduced-config smoke tests + decode/prefill consistency."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import (decode_step, forward_hidden, forward_loss,
                          init_cache, init_params, lm_logits, prefill)
from repro.models.model import pattern_stages

from conftest import tiny_batch


def _reduced(arch, **kw):
    cfg = get_config(arch).reduced()
    return dataclasses.replace(cfg, **kw) if kw else cfg


# --------------------------------------------------------------------------
# (f) REQUIRED smoke tests: one forward/train step, shapes + no NaNs
# --------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_grad(arch):
    cfg = _reduced(arch)
    p = init_params(cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(cfg, B=2, T=16)

    def loss_fn(p):
        return forward_loss(p, cfg, batch)

    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
    assert jnp.isfinite(loss), arch
    assert float(loss) > 0
    # output logits shape
    h, _ = forward_hidden(p, cfg, batch["tokens"],
                          pos3=batch.get("pos3"),
                          patch_embeds=batch.get("patch_embeds"),
                          patch_pos=batch.get("patch_pos"),
                          enc_out=None if not cfg.enc_dec else
                          jnp.zeros((2, cfg.enc_len, cfg.d_model),
                                    jnp.bfloat16))
    assert h.shape == (2, 16, cfg.d_model)
    logits = lm_logits(p, cfg, h)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    # gradients exist, are finite, and at least one is nonzero
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
               for g in leaves)
    assert any(float(jnp.max(jnp.abs(g.astype(jnp.float32)))) > 0
               for g in leaves)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_reduces_loss(arch):
    """A few SGD steps on one repeated batch must reduce the loss."""
    cfg = _reduced(arch)
    p = init_params(cfg, jax.random.PRNGKey(1))
    batch = tiny_batch(cfg, B=2, T=16, seed=3)

    @jax.jit
    def step(p):
        (loss, _), g = jax.value_and_grad(
            lambda p: forward_loss(p, cfg, batch), has_aux=True)(p)
        p = jax.tree_util.tree_map(
            lambda w, gw: (w.astype(jnp.float32)
                           - 0.05 * gw.astype(jnp.float32)).astype(w.dtype),
            p, g)
        return p, loss

    p, l0 = step(p)
    for _ in range(5):
        p, l1 = step(p)
    assert float(l1) < float(l0), arch


# --------------------------------------------------------------------------
# decode == training forward (teacher forcing) per family
# --------------------------------------------------------------------------
DECODE_ARCHS = ["qwen3_0_6b", "qwen2_1_5b", "xlstm_1_3b", "zamba2_2_7b",
                "mixtral_8x22b", "moonshot_v1_16b_a3b", "whisper_small"]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_decode_matches_forward(arch):
    """prefill(t[:k]) + decode one-by-one == full forward logits, fp32."""
    cfg = _reduced(arch, param_dtype="float32")
    if cfg.sliding_window:
        # make the window cover the test sequence: rolling correctness is
        # tested separately below
        cfg = dataclasses.replace(cfg, sliding_window=64)
    p = init_params(cfg, jax.random.PRNGKey(2))
    B, T, k = 2, 12, 8
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    frames = (jnp.asarray(rng.normal(size=(B, cfg.enc_len, cfg.d_model))
                          * 0.02, jnp.float32) if cfg.enc_dec else None)

    enc = None
    kwargs = {}
    if cfg.enc_dec:
        from repro.models.model import encode
        enc = encode(p, cfg, frames)
        kwargs["frames"] = frames
    h, _ = forward_hidden(p, cfg, toks, enc_out=enc)
    full_logits = lm_logits(p, cfg, h)                  # [B, T, V]
    if cfg.mla:
        # no latent cache yet: serving refuses rather than computes wrong
        for serve in (lambda: prefill(p, cfg, toks[:, :k]),
                      lambda: init_cache(cfg, B, T)):
            with pytest.raises(NotImplementedError, match="latent cache"):
                serve()
        return

    logits_k, cache = prefill(p, cfg, toks[:, :k], pad=T - k + 4, **kwargs)
    np.testing.assert_allclose(np.asarray(logits_k, np.float32),
                               np.asarray(full_logits[:, k - 1], np.float32),
                               rtol=2e-3, atol=2e-3)
    for i in range(k, T):
        logits_i, cache = decode_step(p, cfg, toks[:, i], cache,
                                      jnp.int32(i))
        np.testing.assert_allclose(
            np.asarray(logits_i, np.float32),
            np.asarray(full_logits[:, i], np.float32),
            rtol=2e-3, atol=2e-3, err_msg=f"{arch} pos {i}")


def test_sliding_window_rolling_cache():
    """Rolling cache (W slots) decode == full forward with windowed mask."""
    cfg = _reduced("mixtral_8x22b", param_dtype="float32")
    W = cfg.sliding_window
    assert W == 64
    p = init_params(cfg, jax.random.PRNGKey(3))
    B, T = 1, 80                                       # longer than the window
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    h, _ = forward_hidden(p, cfg, toks)
    full_logits = lm_logits(p, cfg, h)

    k = 70                                             # prefill beyond window
    logits_k, cache = prefill(p, cfg, toks[:, :k])
    np.testing.assert_allclose(np.asarray(logits_k, np.float32),
                               np.asarray(full_logits[:, k - 1], np.float32),
                               rtol=3e-3, atol=3e-3)
    for i in range(k, T):
        logits_i, cache = decode_step(p, cfg, toks[:, i], cache, jnp.int32(i))
        np.testing.assert_allclose(
            np.asarray(logits_i, np.float32),
            np.asarray(full_logits[:, i], np.float32),
            rtol=3e-3, atol=3e-3, err_msg=f"pos {i}")


# --------------------------------------------------------------------------
# structural checks
# --------------------------------------------------------------------------
def test_zamba2_pattern_and_shared_block():
    cfg = _reduced("zamba2_2_7b")
    stages = pattern_stages(cfg)
    assert all(k == "mamba2" for k, _ in stages)
    assert sum(c for _, c in stages) == cfg.n_layers
    assert len(stages) > 1                             # cut at shared-attn
    p = init_params(cfg, jax.random.PRNGKey(0))
    assert "shared" in p


def test_xlstm_pattern_ratio():
    cfg = get_config("xlstm_1_3b")
    kinds = cfg.block_pattern
    n_s = sum(1 for k in kinds if k == "slstm")
    n_m = sum(1 for k in kinds if k == "mlstm")
    assert n_s > 0 and n_m > 0
    assert n_m / n_s >= 5                              # mostly mLSTM


def test_moe_router_balance_aux_positive():
    cfg = _reduced("mixtral_8x22b")
    p = init_params(cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(cfg, B=2, T=16)
    _, metrics = forward_loss(p, cfg, batch)
    assert float(metrics["aux"]) > 0                   # load-balance loss


def test_vlm_patch_embedding_injected():
    cfg = _reduced("qwen2_vl_7b")
    p = init_params(cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(cfg, B=2, T=16)
    h1, _ = forward_hidden(p, cfg, batch["tokens"], pos3=batch["pos3"],
                           patch_embeds=batch["patch_embeds"],
                           patch_pos=batch["patch_pos"])
    h2, _ = forward_hidden(p, cfg, batch["tokens"], pos3=batch["pos3"],
                           patch_embeds=batch["patch_embeds"] + 1.0,
                           patch_pos=batch["patch_pos"])
    assert float(jnp.max(jnp.abs((h1 - h2).astype(jnp.float32)))) > 0


def test_whisper_encoder_affects_decoder():
    cfg = _reduced("whisper_small")
    p = init_params(cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(cfg, B=2, T=16)
    loss1, _ = forward_loss(p, cfg, batch)
    batch2 = dict(batch)
    batch2["frames"] = batch["frames"] + 1.0
    loss2, _ = forward_loss(p, cfg, batch2)
    assert abs(float(loss1) - float(loss2)) > 1e-6


def test_label_mask_ignore_index():
    cfg = _reduced("qwen3_0_6b")
    p = init_params(cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(cfg, B=2, T=16)
    full, m1 = forward_loss(p, cfg, batch)
    masked = dict(batch)
    masked["labels"] = batch["labels"].at[:, 8:].set(-1)
    part, m2 = forward_loss(p, cfg, masked)
    assert float(m2["ntokens"]) < float(m1["ntokens"])
    assert jnp.isfinite(part)
    all_masked = dict(batch)
    all_masked["labels"] = jnp.full_like(batch["labels"], -1)
    zero, m3 = forward_loss(p, cfg, all_masked)
    assert float(m3["ntokens"]) == 0
    assert jnp.isfinite(zero)                         # no div-by-zero NaN


def test_pallas_attention_refuses_non_tpu_backend():
    """attn_impl='pallas' never falls back to the Pallas interpreter."""
    cfg = dataclasses.replace(_reduced("qwen3_0_6b"), attn_impl="pallas")
    p = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(RuntimeError, match="compiles for the TPU"):
        forward_loss(p, cfg, tiny_batch(cfg, B=1, T=16))
