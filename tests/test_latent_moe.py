"""Multi-head latent attention, the drop-free MoE layer and a chip's share
of a config, in the program alone (the comparison with the float32
reference is chipbench/test_chipbench_deepseek.py)."""
from __future__ import annotations

import dataclasses
import math
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention, init_params
from repro.models.attention import attend_chunked, attend_naive

from conftest import tiny_batch


def _naive(q, k, v):
    """Causal softmax attention written out, v of its own width."""
    T = q.shape[1]
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)


def _qkv(vd, T=1024, H=2, hd=24):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (1, T, H, hd)),
            jax.random.normal(ks[1], (1, T, H, hd)),
            jax.random.normal(ks[2], (1, T, H, vd)))


def test_attend_chunked_with_v_narrower_than_q():
    q, k, v = _qkv(vd=16)
    with jax.default_matmul_precision("highest"):
        out = attend_chunked(q, k, v, causal=True, window=0)
        want = _naive(q, k, v)
    assert out.shape == (1, 1024, 2, 16)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def test_attend_chunked_with_equal_widths_is_unchanged(monkeypatch):
    """Equal widths: the same values as the naive form; recomputing each
    block in the backward (large inputs) changes neither values nor
    gradients."""
    q, k, v = _qkv(vd=24)

    def run():
        f = lambda q, k, v: attend_chunked(q, k, v, causal=True, window=0)
        return f(q, k, v), jax.grad(lambda *a: jnp.sum(f(*a) ** 2),
                                    argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        out, grads = run()
        np.testing.assert_allclose(
            out, attend_naive(q, k, v, causal=True, window=0),
            rtol=1e-5, atol=1e-5)
        monkeypatch.setattr(attention, "SAVED_PROBS_BYTES", 0)
        out2, grads2 = run()
    np.testing.assert_array_equal(out, out2)
    for a, b in zip(grads, grads2):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_moonlight_is_the_published_model():
    cfg = get_config("moonlight-16b-a3b")
    assert cfg is get_config("moonshot_v1_16b_a3b")
    assert cfg.block_pattern == ("attn",) + ("moe",) * 26
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.n_shared_experts, cfg.d_ff,
            cfg.d_ff_expert) == (64, 6, 2, 11264, 1408)
    assert (cfg.router_score, cfg.topk_method, cfg.routed_scaling,
            cfg.rope_theta, cfg.norm_eps) == (
                "sigmoid", "noaux_tc", 2.446, 50_000.0, 1e-5)


def test_reduced_keeps_the_dense_first_layer_and_latent_widths():
    r = get_config("moonlight-16b-a3b").reduced()
    assert r.block_pattern == ("attn",) + ("moe",) * (r.n_layers - 1)
    assert r.kv_lora_rank and r.qk_rope_head_dim and r.v_head_dim
    assert r.qk_nope_head_dim + r.qk_rope_head_dim != r.v_head_dim
    p = init_params(r, jax.random.PRNGKey(0))
    moe = p["stages"][1]["moe"]
    assert moe["router"].shape[-1] == r.n_experts
    assert moe["w_gate"].shape[1] == r.n_held
    assert "shared" in moe and "e_bias" in moe
    assert set(p["stages"][0]["attn"]) == {"wq", "wkv_a", "kv_a_norm",
                                           "wkv_b", "wo"}


def test_chip_share_cuts_depth_experts_and_vocabulary():
    full = get_config("moonlight-16b-a3b")
    chip = get_config("moonlight-16b-a3b.chip")
    assert chip == dataclasses.replace(
        full.chip_share("moonlight-16b-a3b.chip", 5, 8, 20480),
        microbatches=1, fsdp=False)
    assert (chip.n_layers, chip.n_experts, chip.n_held, chip.vocab_size,
            chip.d_model, chip.top_k) == (5, 64, 8, 20480, 2048, 6)
    assert chip.block_pattern == ("attn",) + ("moe",) * 4
    # the guide's floors: a period and four layers after the dense one, 8
    # experts, an eighth of the vocabulary
    assert 568e6 < chip.param_count() < 569e6


def _tiny_moe(**kw):
    cfg = get_config("moonlight-16b-a3b").reduced()
    return dataclasses.replace(cfg, remat="none", **kw)


def test_trainer_reports_every_routed_row(monkeypatch):
    """With every expert held, each MoE block computes exactly tokens x
    top_k rows a step (none dropped): the trainer writes their sum and the
    busiest expert's in the repro.train.moe span after the sync."""
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import make_batch_fn
    from repro.launch.mesh import make_host_mesh
    from repro.train import trainer as trainer_mod
    cfg = _tiny_moe(experts_held=0)
    spans = []
    real = trainer_mod.TraceAnnotation

    class Recorded(real):
        def __init__(self, name, **kw):
            spans.append((name, kw))
            super().__init__(name, **kw)

    monkeypatch.setattr(trainer_mod, "TraceAnnotation", Recorded)
    B, T = 2, 32
    tr = trainer_mod.Trainer(
        cfg, make_host_mesh(1, 1),
        make_batch_fn(cfg, ShapeConfig("t", T, B, "train"), seed=1),
        trainer_mod.TrainerConfig(ckpt_dir=tempfile.mkdtemp(),
                                  ckpt_every=10**9, log_every=10**9),
        log=lambda s: None)
    tr.run(2)
    names = [n for n, _ in spans]
    assert names.count("repro.train.moe") == 2
    i = names.index("repro.train.moe")
    assert names[i - 1] == "repro.train.sync"
    moe = spans[i][1]
    n_moe = cfg.n_layers - cfg.first_k_dense
    assert moe["step"] == 0 and moe["tokens"] == B * T
    assert moe["rows"] == n_moe * B * T * cfg.top_k
    assert B * T * cfg.top_k / cfg.n_experts <= moe["rows_max"] <= B * T


def test_a_dense_config_reports_no_rows():
    from repro.models import forward_loss
    cfg = get_config("qwen3_0_6b").reduced()
    p = init_params(cfg, jax.random.PRNGKey(0))
    _, metrics = forward_loss(p, cfg, tiny_batch(cfg))
    assert "expert_rows" not in metrics


def test_the_serve_engine_refuses_latent_attention():
    from repro.serve.engine import ServeEngine
    cfg = _tiny_moe()
    p = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="latent cache"):
        ServeEngine(cfg, p, slots=2, max_seq=64)
