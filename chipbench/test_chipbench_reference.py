"""The plain reference against the program's model at reduced widths.

Both run in float32 on the CPU, so they may differ by float32 round-off
alone. Covers qk-norm (qwen3), q/k/v biases (qwen2), GQA, RoPE and tied
embeddings, the next-token loss and its gradient."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, spec, traffic_gen, weights

SMALL = {"num_hidden_layers": 2, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "intermediate_size": 96, "vocab_size": 128, "param_dtype": "float32"}


def _setup(config_name):
    base = spec.load_json(spec.HERE / "configs" / f"{config_name}.json")
    c = {**base, **SMALL, "program": {**base["program"], "remat": "none"}}
    cfg = spec.arch_config(c, exact=False)
    w = weights.maker(c)(weights.seed_key(3))
    tokens = jnp.asarray(traffic_gen.train_batch(3, 0, 2, 32, 128))
    return c, cfg, w, tokens


@pytest.mark.parametrize("config_name", ["qwen3-0.6b", "qwen2-1.5b"])
def test_logits_match_program(config_name):
    from repro.models.model import forward_hidden, lm_logits
    c, cfg, w, tokens = _setup(config_name)
    with jax.default_matmul_precision("highest"):
        p = spec.arch(c).to_program(w)
        h, _ = forward_hidden(p, cfg, tokens)
        prog = lm_logits(p, cfg, h).astype(jnp.float32)
        ref = reference.head(w, spec.arch(c).hidden(w, c, tokens))
    scale = float(jnp.max(jnp.abs(ref)))
    # the program's logits come out in bf16: a relative 2^-8 of the largest
    assert float(jnp.max(jnp.abs(prog - ref))) <= scale / 128


@pytest.mark.parametrize("config_name", ["qwen3-0.6b", "qwen2-1.5b"])
def test_loss_and_grads_match_program(config_name):
    from repro.models.model import forward_loss
    c, cfg, w, tokens = _setup(config_name)
    batch = {"tokens": tokens, "labels": tokens}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: forward_loss(p, cfg, batch)[0])(
            spec.arch(c).to_program(w))
        lr, gr = jax.value_and_grad(reference.loss)(w, c, tokens)
    assert abs(float(lp) - float(lr)) < 2e-2    # bf16 logits in the program
    gp = weights.flat_norms(weights.leaf_norms(
        spec.arch(c).from_program(gp), c), c)
    gr = weights.flat_norms(weights.leaf_norms(gr, c), c)
    assert gp.keys() == gr.keys()
    for k in gr:
        assert gp[k] == pytest.approx(gr[k], rel=5e-2, abs=1e-6), k


@pytest.mark.parametrize("module", ["reference", "weights",
                                    "archs/qwen_dense"])
def test_reference_is_independent_of_the_program(module):
    import re
    src = (spec.HERE / f"{module}.py").read_text()
    assert not re.search(r"^\s*(from|import)\s+repro\b", src, re.M)


def test_fp8_control_rounds():
    x = jnp.linspace(-1, 1, 101)
    y = reference._fp8(x)
    assert float(jnp.max(jnp.abs(y - x))) > 1e-3      # it does round
    assert float(jnp.max(jnp.abs(y - x))) < 0.07      # to 3 mantissa bits
    g = jax.grad(lambda v: jnp.sum(reference._fp8(v) * x))(x)
    assert np.all(np.isfinite(np.asarray(g)))
