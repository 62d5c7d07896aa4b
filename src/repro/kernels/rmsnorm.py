"""Pallas TPU fused RMSNorm kernel (row-tiled, fp32 reduction in VMEM)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)          # [rows, d]
    g = g_ref[...].astype(jnp.float32)          # [d]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps) * g).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, gain, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = False):
    """x: [..., d]; gain: [d]. Fused norm, fp32 internals."""
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    xf = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    nb = xf.shape[0] // block_rows

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xf, gain)
    if pad:
        out = out[:rows]
    return out.reshape(shape)
