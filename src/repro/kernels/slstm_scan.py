"""Pallas TPU kernel for the sLSTM time scan (per-head, VMEM-resident R).

The sLSTM recurrence is inherently sequential in time; on the HLO path each
timestep re-reads the recurrent matrices from HBM (~17 MB x 4096 steps x
layer — the xlstm-1.3b train cell's dominant memory term, see EXPERIMENTS.md
§Perf). This kernel is the TPU-native fix: one program per head keeps its
recurrence block R_h [dh, 4dh] pinned in VMEM across ALL timesteps (the
grid's time dimension is "arbitrary"/sequential and R_h's index_map is
time-invariant, so it is fetched once), carries the (c, n, m, h) state in
VMEM scratch, and streams wx through in T-chunks.

Math matches repro.models.xlstm._slstm_cell exactly (stabilized
exponential gating):

    pre  = wx_t + h_{t-1} @ R_h + b_h           (gate-major [i, f, z, o])
    m_t  = max(log_sigmoid(f) + m, min(i, I_CLAMP))
    c_t  = exp(f_log + m - m_t) c + exp(i_log - m_t) tanh(z)
    n_t  = exp(f_log + m - m_t) n + exp(i_log - m_t)
    h_t  = sigmoid(o) * c_t / max(n_t, 1)

Forward-only (serving / prefill); training uses the chunk-rematerialized
jnp scan in repro.models.xlstm. Validated vs ref.slstm_ref in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I_CLAMP = 15.0


def _slstm_kernel(wx_ref, r_ref, b_ref, hs_ref, c_ref, n_ref, m_ref, h_ref,
                  *, chunk: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        h_ref[...] = jnp.zeros_like(h_ref)

    r = r_ref[0].astype(jnp.float32)           # [dh, 4dh] — VMEM-resident
    b = b_ref[0].astype(jnp.float32)           # [4dh]
    dh = r.shape[0]

    def step(t, _):
        wx_t = wx_ref[0, t].astype(jnp.float32)          # [B, 4dh]
        h_prev = h_ref[...]
        rec = jax.lax.dot_general(h_prev, r, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        pre = wx_t + rec + b
        i_r = pre[:, 0 * dh:1 * dh]
        f_r = pre[:, 1 * dh:2 * dh]
        z_r = pre[:, 2 * dh:3 * dh]
        o_r = pre[:, 3 * dh:4 * dh]
        i_log = jnp.minimum(i_r, I_CLAMP)
        f_log = jax.nn.log_sigmoid(f_r)
        m_prev = m_ref[...]
        m_new = jnp.maximum(f_log + m_prev, i_log)
        ig = jnp.exp(i_log - m_new)
        fg = jnp.exp(f_log + m_prev - m_new)
        c_new = fg * c_ref[...] + ig * jnp.tanh(z_r)
        n_new = fg * n_ref[...] + ig
        h_new = jax.nn.sigmoid(o_r) * c_new / jnp.maximum(n_new, 1.0)
        c_ref[...] = c_new
        n_ref[...] = n_new
        m_ref[...] = m_new
        h_ref[...] = h_new
        hs_ref[0, t] = h_new.astype(hs_ref.dtype)
        return ()

    jax.lax.fori_loop(0, chunk, step, ())


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def slstm_scan(wx, r, b, *, chunk: int = 64, interpret: bool = False):
    """wx: [B, T, nh, 4dh] (input projection, gate-major per head);
    r: [nh, dh, 4dh]; b: [nh, 4dh]. Returns hs: [B, T, nh, dh].

    Grid (head, T-chunk); the chunk dim is sequential and carries the
    per-head (c, n, m, h) state in VMEM scratch.
    """
    B, T, nh, gd = wx.shape
    dh = gd // 4
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk

    wx_h = wx.transpose(2, 1, 0, 3)             # [nh, T, B, 4dh]
    out = pl.pallas_call(
        functools.partial(_slstm_kernel, chunk=chunk),
        grid=(nh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, B, gd), lambda h, t: (h, t, 0, 0)),
            pl.BlockSpec((1, dh, gd), lambda h, t: (h, 0, 0)),
            pl.BlockSpec((1, gd), lambda h, t: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, B, dh), lambda h, t: (h, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nh, T, B, dh), wx.dtype),
        scratch_shapes=[pltpu.VMEM((B, dh), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(wx_h, r, b)
    return out.transpose(2, 1, 0, 3)            # [B, T, nh, dh]
