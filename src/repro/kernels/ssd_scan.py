"""Pallas TPU kernel for the SSD chunked linear recurrence (Mamba-2 / mLSTM).

One program per (batch*head, chunk); the chunk grid dimension is sequential
("arbitrary") and carries the [N, P] state in VMEM scratch — the TPU-native
replacement for the GPU warp-level chunk scan: intra-chunk work is dense MXU
matmuls ([Q,Q] and [Q,N]x[N,P]), inter-chunk state is a VMEM-resident
accumulator instead of shared-memory shuffles.

Engine layout matches repro.models.ssm.ssd_chunked: heads pre-expanded
(groups repeated), decays in log space (<= 0 for stability).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, s_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0].astype(jnp.float32)           # [Q, P]
    a = a_ref[0].astype(jnp.float32)           # [Q]
    b = b_ref[0].astype(jnp.float32)           # [Q, N]
    c = c_ref[0].astype(jnp.float32)           # [Q, N]

    a_cum = jnp.cumsum(a)                      # [Q]
    a_tot = a_cum[-1]

    # intra-chunk: scores[i, j] = (c_i . b_j) * exp(a_cum_i - a_cum_j), i>=j
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    logdecay = a_cum[:, None] - a_cum[None, :]
    L = jnp.where(li >= lj, jnp.exp(logdecay), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y += (c @ S_prev) * exp(a_cum)
    s_prev = s_ref[...]                        # [N, P]
    y = y + jax.lax.dot_general(c, s_prev, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        * jnp.exp(a_cum)[:, None]

    # state update: S = exp(a_tot) * S_prev + B^T (x * exp(a_tot - a_cum))
    xw = x * jnp.exp(a_tot - a_cum)[:, None]
    s_ref[...] = jnp.exp(a_tot) * s_prev + jax.lax.dot_general(
        b, xw, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, a, B, C, *, chunk: int = 256, interpret: bool = False):
    """x: [b, T, H, P]; a: [b, T, H]; B/C: [b, T, H, N] (groups expanded).

    Returns y: [b, T, H, P]. Final state stays internal (training path);
    decode uses repro.models.ssm.ssd_decode_step.
    """
    b, T, H, P = x.shape
    N = B.shape[-1]
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk

    # layout: [b*H, T, *] — contiguous per (batch, head) program
    xt = x.transpose(0, 2, 1, 3).reshape(b * H, T, P)
    at = a.transpose(0, 2, 1).reshape(b * H, T)
    Bt = B.transpose(0, 2, 1, 3).reshape(b * H, T, N)
    Ct = C.transpose(0, 2, 1, 3).reshape(b * H, T, N)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(b * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk), lambda h, c: (h, c)),
            pl.BlockSpec((1, chunk, N), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda h, c: (h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b * H, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xt, at, Bt, Ct)
    return out.reshape(b, H, T, P).transpose(0, 2, 1, 3)
