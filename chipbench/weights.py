"""Random weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights, in the type they are served in, and hands
them to the program; the reference gets the same weights by making them
again from the seed. Layout (per-layer leaves stacked on a leading axis):

    embed [V, d]   final_norm [d]   (lm_head [V, d] when untied)
    ln1 ln2 [L, d]   wq [L, d, H*hd]   wk wv [L, d, KV*hd]   wo [L, H*hd, d]
    bq bk bv [L, width]   (attention_bias)   q_norm k_norm [L, hd] (qk_norm)
    w_gate w_up [L, d, F]   w_down [L, F, d]

``to_program`` / ``from_program`` rename these to and from the program's
parameter tree (repro.models.model.init_params): the only place the
benchmark depends on that layout.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
STACKED = ("ln1", "wq", "wk", "wv", "wo", "bq", "bk", "bv", "q_norm",
           "k_norm", "ln2", "w_gate", "w_up", "w_down")
ATTN = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "q_norm", "k_norm")
MLP = ("w_gate", "w_up", "w_down")


def seed_key(seed: int):
    """A PRNG key from any whole number, however large."""
    a, b = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a)), int(b))


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


def _std(c, name: str, shape) -> float:
    if name in ("embed", "lm_head"):
        return 0.02
    if name in ("bq", "bk", "bv"):
        return 0.1
    std = 1.0 / math.sqrt(shape[-2])
    if name in ("wo", "w_down"):
        std /= math.sqrt(2 * c["num_hidden_layers"])
    return std


def rounded(x, dtype):
    """Float32 ``x`` rounded to the values of ``dtype`` (to the nearest,
    ties to even), still float32. A reduce_precision, not a pair of
    converts: XLA may drop a float32 -> bfloat16 -> float32 pair and keep
    the float32 value, and on the TPU it does."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def make(c: Dict[str, Any], key) -> Dict[str, jax.Array]:
    dtype = DTYPES[c["param_dtype"]]
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(c).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name in ("ln1", "ln2", "final_norm", "q_norm", "k_norm"):
            w = 1.0 + 0.1 * z
        else:
            w = z * _std(c, name, shape)
        # rounded first, so that a caller that takes the weights back to
        # float32 in the same program still sees the stored values
        out[name] = rounded(w, dtype).astype(dtype)
    return out


def maker(c: Dict[str, Any], shardings=None):
    """``fn(key)`` -> weights, jitted; ``shardings`` in this layout."""
    return jax.jit(lambda key: make(c, key), out_shardings=shardings)


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


def leaf_norms(w: Dict[str, Any]) -> Dict[str, jax.Array]:
    """L2 norm of each leaf, per layer for stacked leaves (float32)."""
    out = {}
    for name, x in w.items():
        x = x.astype(jnp.float32)
        if name in STACKED:
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(
                range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))[None]
    return out


def flat_norms(norms: Dict[str, Any]) -> Dict[str, float]:
    """{"wq.3": norm of layer 3 of wq, "embed": ...} on the host."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if name in STACKED:
            out.update({f"{name}.{i}": float(x) for i, x in enumerate(v)})
        else:
            out[name] = float(v[0])
    return out
