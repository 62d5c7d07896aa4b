"""The DeepSeek-V3 family (``archs/deepseek_moe.py``) against the program,
at a small size on the CPU with seeded random weights: the Trainer's step
against the float32 reference, the chip's share of an expert layer against
the uncut layer, an adversarial router, the selection bias, the counts,
and a reduced copy of the ``moonlight-16b-a3b.train`` cell through
``run_cell``."""
from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import drive_train, flops, spec
from chipbench.record import Run
from chipbench.run import CompileCounter, Tracer, run_cell

CELL = "moonlight-16b-a3b.train"
ARCH = spec.arch({"bench_arch": "deepseek_moe"})
# one dense layer, two expert layers of 4 held experts out of 8 routed
DS_TINY = {"num_hidden_layers": 3, "hidden_size": 64,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "intermediate_size": 128, "kv_lora_rank": 32,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
           "moe_intermediate_size": 32, "router_experts": 8,
           "n_routed_experts": 4, "num_experts_per_tok": 2,
           "n_shared_experts": 1, "vocab_size": 256}


def tiny_cell(**config):
    cell = spec.load_cell(CELL)
    cell.config = {**cell.config, **DS_TINY, **config,
                   "program": {**cell.config["program"], "remat": "none"}}
    cell.traffic = {**cell.traffic, "seq_len": 64}
    return cell


def _program_cfg(**kw):
    """The program's config of the tiny cell."""
    return dataclasses.replace(
        spec.arch_config(tiny_cell(param_dtype="float32").config,
                         exact=False), **kw)


def _layer(cfg, key):
    """One expert layer's weights in the program's layout, float32, with a
    seeded selection bias."""
    from repro.models.mlp import init_moe_params
    p = init_moe_params(key, cfg, jnp.float32)
    p["e_bias"] = 0.05 * jax.random.normal(jax.random.fold_in(key, 9),
                                           (cfg.n_experts,))
    return p


def _reference_layer(cfg, p, x):
    """The reference's expert layer (shared experts included) on the
    program's weights."""
    c = {**tiny_cell().config, "router_experts": cfg.n_experts,
         "n_routed_experts": cfg.n_held, "num_experts_per_tok": cfg.top_k}
    lw = {"router": p["router"], "e_bias": p["e_bias"], "w_gate": p["w_gate"],
          "w_up": p["w_up"], "w_down": p["w_down"],
          **{k: p["shared"][v] for k, v in ARCH.SHARED.items()}}
    with jax.default_matmul_precision("highest"):
        return ARCH._moe(c, "f32", x, lw)


def test_the_chip_config_is_the_files():
    cfg = spec.arch_config(spec.load_cell(CELL).config)
    assert (cfg.name, cfg.n_layers, cfg.n_experts, cfg.n_held,
            cfg.vocab_size) == ("moonlight-16b-a3b.chip", 5, 64, 8, 20480)
    assert cfg.block_pattern == ("attn",) + ("moe",) * 4
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.d_ff, cfg.d_ff_expert) == (
                512, 128, 64, 128, 11264, 1408)
    assert (cfg.top_k, cfg.n_shared_experts, cfg.router_score,
            cfg.routed_scaling) == (6, 2, "sigmoid", 2.446)


def test_a_file_that_does_not_normalise_the_chosen_weights_is_refused():
    """The program always normalises the chosen experts' weights, so the
    family refuses a file whose ``norm_topk_prob`` is false."""
    c = spec.load_cell(CELL).config
    assert ARCH.shapes(c)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        ARCH.shapes({**c, "norm_topk_prob": False})


def test_the_cell_limits_lie_between_their_readings():
    """Each limit of the cell lies above its sound runs' largest reading
    and below every reading of the fp8 control and the planted faults, so
    check.judge passes the first and fails each of the others."""
    from chipbench import check
    cell = json.loads((spec.HERE / "cells" / f"{CELL}.json").read_text())
    assert set(cell["limits"]) == {"grad_gap", "change_gap"}
    for name, limit in cell["limits"].items():
        r = cell["readings"][name]
        assert check.judge({name: r["lower"]}, {name: limit})[0], name
        assert "control" in r["upper"], name
        for fault, v in r["upper"].items():
            assert not check.judge({name: v}, {name: limit})[0], (name,
                                                                  fault)


def test_trainer_step_matches_the_reference():
    """bfloat16 through Trainer.run against the float32 reference: each
    step's loss, the first step's gradient norm of every leaf and the
    3-step change of every leaf; the fp8 control lies further off."""
    run = Run(tiny_cell(), 2**31 + 5, 1, None)
    readings, *_ = drive_train.drive(run, 0.0, Tracer(run, False),
                                     CompileCounter(), time.monotonic(),
                                     exact=False)
    numbers = drive_train.numbers(run, readings, control=True)
    assert numbers["loss_gap"] < 0.01, numbers
    assert numbers["grad_gap"] < 0.03, numbers
    assert numbers["change_gap"] < 0.03, numbers
    assert numbers["control_grad_gap"] > 2 * numbers["grad_gap"], numbers
    assert set(readings["grad_norms"]) >= {"e_bias.0", "w_gate.1",
                                           "dense_w_down.0", "kv_a_norm.1",
                                           "shared_up.0", "router.1"}


def test_forward_and_gradients_match_the_reference_in_float32():
    """The decoder stack in float32 (the program's logits are bfloat16, so
    this compares the final-normed hidden states): values, and the norm of
    every leaf's gradient of a seeded projection of them."""
    from chipbench import reference, weights
    from repro.models import forward_hidden
    from repro.models.common import rms_norm
    c = tiny_cell(param_dtype="float32").config
    cfg = spec.arch_config(c, exact=False)
    w = weights.maker(c)(weights.seed_key(7))
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 64), 0, 256)
    probe = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 64))

    def program(w):
        p = ARCH.to_program(w)
        h, _ = forward_hidden(p, cfg, tokens)
        return rms_norm(h, p["final_norm"], cfg.norm_eps)

    with jax.default_matmul_precision("highest"):
        want = ARCH.hidden(w, c, tokens)
        got = program(w)
        g_ref = jax.grad(lambda w: jnp.sum(ARCH.hidden(w, c, tokens)
                                           * probe))(w)
        g = jax.grad(lambda w: jnp.sum(program(w) * probe))(w)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    got = weights.flat_norms(weights.leaf_norms(g, c), c)
    want = weights.flat_norms(weights.leaf_norms(g_ref, c), c)
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-3, abs=1e-6), k


def test_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of 2 experts each: their routed parts summed,
    with the shared experts counted once, give the layer with all 8."""
    from repro.models.mlp import mlp_forward, moe_forward
    cfg = _program_cfg(experts_held=0)
    p = _layer(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model))
    whole, _, rows = moe_forward(p, cfg, x)
    shared = mlp_forward(p["shared"], cfg, x.reshape(-1, cfg.d_model))
    share = dataclasses.replace(cfg, experts_held=2)
    total, share_rows = shared.reshape(x.shape), []
    for s in range(4):
        ps = {**p, **{k: p[k][2 * s:2 * s + 2]
                      for k in ("w_gate", "w_up", "w_down")}}
        y, _, r = moe_forward(ps, share, x, first_expert=2 * s)
        total = total + (y - shared.reshape(x.shape))
        share_rows.append(r)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    assert np.concatenate(share_rows).tolist() == rows.tolist()
    assert int(rows.sum()) == 2 * 16 * cfg.top_k
    np.testing.assert_allclose(whole, _reference_layer(cfg, p, x),
                               rtol=1e-4, atol=1e-4)


def test_a_router_that_sends_every_token_to_one_expert_drops_none():
    from repro.models.mlp import moe_forward
    cfg = _program_cfg()
    p = _layer(cfg, jax.random.PRNGKey(3))
    # the bias makes every token choose experts 0 and 1, so held expert 0
    # gets all 32 tokens: 8 times its mean load
    p["e_bias"] = jnp.zeros((cfg.n_experts,)).at[:2].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    y, _, rows = moe_forward(p, cfg, x)
    assert rows.tolist() == [32, 32, 0, 0]
    np.testing.assert_allclose(y, _reference_layer(cfg, p, x),
                               rtol=1e-4, atol=1e-4)


def test_the_bias_changes_the_choice_not_the_weights():
    from repro.models.mlp import route
    cfg = _program_cfg()
    p = _layer(cfg, jax.random.PRNGKey(5))
    xf = jax.random.normal(jax.random.PRNGKey(6), (32, cfg.d_model))
    p["e_bias"] = jnp.zeros((cfg.n_experts,))
    free, _, _ = route(p, cfg, xf)
    p["e_bias"] = p["e_bias"].at[-2:].set(10.0)
    top, weight, _ = route(p, cfg, xf)
    assert sorted(set(np.asarray(top).ravel())) == [6, 7]
    assert not np.array_equal(np.sort(free, -1), np.sort(top, -1))
    scores = jax.nn.sigmoid(xf @ p["router"])
    want = jnp.take_along_axis(scores, top, -1)
    want = want / want.sum(-1, keepdims=True) * cfg.routed_scaling
    np.testing.assert_allclose(weight, want, rtol=1e-5)


def test_counts_of_the_chip_share():
    c = spec.load_cell(CELL).config
    # 5 x 13.76 M of latent attention, 69.2 M dense, 4 x (router, shared,
    # 0.75 held experts), 41.9 M LM head
    assert ARCH.matmul_params(c) == 275_644_416
    assert ARCH.attn_pair_flops(c) == 2 * 5 * 16 * (128 + 64 + 128)
    assert ARCH.cache_bytes_per_token(c) == 5 * 576 * 2
    assert ARCH.param_bytes(c) == 2 * 568_484_608
    assert flops.train_step(c, 2, 8192) == pytest.approx(37.4e12, rel=0.01)
    rows = 4 * 12288
    assert ARCH.expert_gmm_flops(c, rows) == 12 * 2 * rows * 2048 * 1408
    assert ARCH.expert_gmm_bytes(c, rows) == 12 * 2 * (
        rows * (2048 + 1408) + 4 * 8 * 2048 * 1408)


# limits for the tiny cell: the cell's own are set for its published
# widths, where sound runs read ~5 times less. Here, over seeds 2**31 + 5,
# 2**31 + 17 and 3000000001, sound runs read grad_gap <= 0.026 and
# change_gap <= 0.0124, the fp8 control >= 0.064 and >= 0.0188
TINY_LIMITS = {"grad_gap": 0.04, "change_gap": 0.015}


def test_a_reduced_copy_of_the_cell_runs_through_run_cell():
    cell = tiny_cell()
    cell.limits = TINY_LIMITS
    out = run_cell(cell, 2**31 + 17, 0.5, False,
                   devices=jax.devices()[:1], peaks=None,
                   t_start=time.monotonic(), exact=False)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
