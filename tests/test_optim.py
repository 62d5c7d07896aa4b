"""Optimizer, LR schedule, and gradient compression."""
from __future__ import annotations

import pytest

pytest.importorskip("hypothesis")
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         cosine_warmup, int8_decode, int8_encode)
from repro.optim.compress import compress_residual


def test_adamw_matches_reference_step():
    """One AdamW step vs a hand-rolled numpy reference (no clip trigger)."""
    rng = np.random.default_rng(0)
    p = {"w": jnp.asarray(rng.normal(size=(4, 4)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    g = {"w": jnp.asarray(rng.normal(size=(4, 4)) * 0.01, jnp.float32),
         "b": jnp.asarray(rng.normal(size=(4,)) * 0.01, jnp.float32)}
    opt = adamw_init(p)
    lr, b1, b2, eps, wd = 1e-3, 0.9, 0.95, 1e-8, 0.1
    newp, newopt, gn = adamw_update(g, opt, p, lr=lr, b1=b1, b2=b2, eps=eps,
                                    weight_decay=wd, max_grad_norm=1e9)
    for k in ("w", "b"):
        gk = np.asarray(g[k], np.float64)
        m = (1 - b1) * gk
        v = (1 - b2) * gk ** 2
        mhat = m / (1 - b1)
        vhat = v / (1 - b2)
        step = mhat / (np.sqrt(vhat) + eps)
        if gk.ndim >= 2:
            step = step + wd * np.asarray(p[k], np.float64)
        want = np.asarray(p[k], np.float64) - lr * step
        np.testing.assert_allclose(np.asarray(newp[k]), want, rtol=1e-5,
                                   atol=1e-6)
    assert int(newopt["count"]) == 1


def test_weight_decay_matrices_only():
    p = {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))}
    g = {"w": jnp.zeros((2, 2)), "b": jnp.zeros((2,))}
    opt = adamw_init(p)
    newp, _, _ = adamw_update(g, opt, p, lr=0.1, weight_decay=0.5)
    assert float(jnp.max(jnp.abs(newp["b"] - 1.0))) < 1e-7   # no decay
    assert float(jnp.max(newp["w"])) < 1.0                   # decayed


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(np.sqrt(90 + 160), rel=1e-6)
    total = np.sqrt(float(sum(jnp.sum(jnp.square(v))
                              for v in jax.tree_util.tree_leaves(clipped))))
    assert total == pytest.approx(1.0, rel=1e-5)
    # under the threshold: unchanged
    same, _ = clip_by_global_norm(g, 1e6)
    np.testing.assert_allclose(np.asarray(same["a"]), np.asarray(g["a"]))


def test_adamw_bf16_state_dtype():
    p = {"w": jnp.ones((8, 8), jnp.bfloat16)}
    opt = adamw_init(p, "bfloat16")
    assert opt["m"]["w"].dtype == jnp.bfloat16
    g = {"w": jnp.full((8, 8), 0.01, jnp.bfloat16)}
    newp, newopt, _ = adamw_update(g, opt, p, lr=1e-2)
    assert newopt["v"]["w"].dtype == jnp.bfloat16
    assert newp["w"].dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(newp["w"].astype(jnp.float32) - 1))) > 0


def test_adamw_converges_quadratic():
    """AdamW drives a quadratic toward its minimum."""
    p = {"x": jnp.asarray([5.0, -3.0])}
    opt = adamw_init(p)

    def loss(p):
        return jnp.sum(jnp.square(p["x"]))

    for _ in range(200):
        g = jax.grad(loss)(p)
        p, opt, _ = adamw_update(g, opt, p, lr=0.1, weight_decay=0.0)
    assert float(loss(p)) < 1e-2


def test_cosine_warmup_shape():
    kw = dict(peak_lr=1.0, warmup_steps=10, total_steps=100)
    assert float(cosine_warmup(jnp.int32(0), **kw)) == 0.0
    assert float(cosine_warmup(jnp.int32(10), **kw)) == pytest.approx(1.0)
    mid = float(cosine_warmup(jnp.int32(55), **kw))
    assert 0.4 < mid < 0.7
    end = float(cosine_warmup(jnp.int32(100), **kw))
    assert end == pytest.approx(0.1, rel=1e-5)       # min_ratio floor
    assert float(cosine_warmup(jnp.int32(5000), **kw)) == pytest.approx(0.1)


# --------------------------------------------------------------------------
# int8 gradient compression
# --------------------------------------------------------------------------
@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_int8_roundtrip_error_bound(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(1000,)) * rng.uniform(0.001, 10),
                    jnp.float32)
    q, scale, pad = int8_encode(x)
    dec = int8_decode(q, scale, pad, x.shape)
    err = np.abs(np.asarray(dec) - np.asarray(x))
    # per-block bound: scale/2 = max|block|/254
    blocks = np.asarray(x).reshape(-1, 250) if x.size % 250 == 0 else None
    bound = np.max(np.abs(np.asarray(x))) / 127.0
    assert err.max() <= bound * 0.51 + 1e-9


def test_int8_shapes_and_pad():
    x = jnp.ones((7, 33))                             # 231 elems: pad to 256
    q, scale, pad = int8_encode(x)
    assert pad == 25
    dec = int8_decode(q, scale, pad, x.shape)
    np.testing.assert_allclose(np.asarray(dec), np.ones((7, 33)), rtol=1e-2)


def test_compress_residual_error_feedback_identity():
    """decoded + residual == original exactly (error feedback invariant)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(513,)), jnp.float32)
    dec, res = compress_residual(x)
    np.testing.assert_allclose(np.asarray(dec) + np.asarray(res),
                               np.asarray(x), rtol=0, atol=1e-6)


def test_compression_ratio():
    x = jnp.zeros((1024,), jnp.float32)
    q, scale, pad = int8_encode(x)
    raw = x.size * 4
    compressed = q.size * 1 + scale.size * 4
    assert compressed < raw / 3.5                     # ~4x minus scale overhead


def test_weight_decay_leaves_vectors_alone_by_name():
    """Every leaf that is a vector per layer (norm gains, biases, the MoE
    selection bias, SSM parameters) is named in NO_DECAY; no matrix is."""
    from repro.configs import ARCH_IDS, get_config
    from repro.models import abstract_params
    from repro.optim.adamw import NO_DECAY, leaf_name
    for arch in ARCH_IDS:
        cfg = get_config(arch).reduced()
        flat = jax.tree_util.tree_flatten_with_path(abstract_params(cfg))[0]
        for path, leaf in flat:
            keys = [str(getattr(k, "key", k)) for k in path]
            stacked = "stages" in keys or "encoder" in keys
            vector = leaf.ndim - stacked < 2
            assert (leaf_name(path) in NO_DECAY) == vector, (arch, keys)


def test_stacked_norm_gains_are_not_decayed():
    p = {"stages": [{"ln1": jnp.ones((3, 4)), "e_bias": jnp.ones((3, 8)),
                     "wq": jnp.ones((3, 4, 4))}]}
    g = jax.tree_util.tree_map(jnp.zeros_like, p)
    newp, _, _ = adamw_update(g, adamw_init(p), p, lr=0.1, weight_decay=0.5)
    st = newp["stages"][0]
    assert float(jnp.max(jnp.abs(st["ln1"] - 1.0))) < 1e-7
    assert float(jnp.max(jnp.abs(st["e_bias"] - 1.0))) < 1e-7
    assert float(jnp.max(st["wq"])) < 1.0
