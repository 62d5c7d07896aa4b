"""Random weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights, in the type they are served in, and hands
them to the program; the reference gets the same weights by making them
again from the seed. The configuration's arch module
(``chipbench/archs/<bench_arch>.py``, ``spec.arch``) gives the leaves and
their shapes (``shapes``), which of them are norm gains (``NORMS``) and
which are stacked per layer (``STACKED``), each other leaf's scale
(``init_std``), and the renaming to and from the program's parameter tree
(``to_program`` / ``from_program``): the only place the benchmark depends
on that tree.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_key(seed: int):
    """A PRNG key from any whole number, however large."""
    a, b = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a)), int(b))


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
    arch, dtype = spec.arch(c), DTYPES[c["param_dtype"]]
    out = {}
    # leaves folded in sorted name order: the same seed, the same bits
    for i, (name, shape) in enumerate(sorted(arch.shapes(c).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name in arch.NORMS:
            w = 1.0 + 0.1 * z
        else:
            w = z * arch.init_std(c, name, shape)
        # rounded first, so that a caller that takes the weights back to
        # float32 in the same program still sees the stored values
        out[name] = rounded(w, dtype).astype(dtype)
    return out


def maker(c: Dict[str, Any], shardings=None):
    """``fn(key)`` -> weights, jitted; ``shardings`` in this layout."""
    return jax.jit(lambda key: make(c, key), out_shardings=shardings)


def leaf_norms(w: Dict[str, Any], c: Dict[str, Any]
               ) -> Dict[str, jax.Array]:
    """L2 norm of each leaf, per layer for stacked leaves (float32)."""
    stacked, out = spec.arch(c).STACKED, {}
    for name, x in w.items():
        x = x.astype(jnp.float32)
        if name in stacked:
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(
                range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))[None]
    return out


def flat_norms(norms: Dict[str, Any], c: Dict[str, Any]
               ) -> Dict[str, float]:
    """{"ln1.3": norm of layer 3 of ln1, "embed": ...} on the host."""
    stacked, out = spec.arch(c).STACKED, {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if name in stacked:
            out.update({f"{name}.{i}": float(x) for i, x in enumerate(v)})
        else:
            out[name] = float(v[0])
    return out
