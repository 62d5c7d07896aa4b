"""Pallas TPU flash attention (causal / sliding-window, GQA).

TPU-native adaptation of the FlashAttention schedule: online softmax over KV
blocks with the running (m, l, acc) state held in VMEM scratch. The grid is
(batch*heads, num_q_blocks, num_kv_blocks) with the KV dimension marked
"arbitrary" (sequential) so scratch accumulates across it; fully-masked KV
blocks are skipped at the block level (causal/window block pruning).

Block shapes are MXU-aligned (multiples of 128 on the matmul dims; head_dim
padding is handled by the wrapper). VMEM working set per step:
  q_blk*hd + kv_blk*hd*2 + q_blk*kv_blk  (fp32 scratch: q_blk*(hd+2))
default (128, 512, hd<=256) < 2 MB — comfortably inside the ~16 MB VMEM.

Validated against ref.attention_ref in interpret mode (tests/test_kernels).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, window: int, q_offset: int, scale: float,
                  block_q: int, block_k: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q + q_offset          # absolute first q position
    k_start = ki * block_k

    # --- block-level pruning ------------------------------------------------
    # block is live unless fully masked: causal => k_start <= q_end;
    # window  => k_end > q_start - window
    q_end = q_start + block_q - 1
    live = True
    if causal:
        live = k_start <= q_end
    if window > 0:
        live = jnp.logical_and(live, k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)       # [bq, hd]
        k = k_ref[0].astype(jnp.float32)       # [bk, hd]
        v = v_ref[0].astype(jnp.float32)       # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window > 0:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                    # [bq]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, block_q: int = 128,
                    block_k: int = 512, interpret: bool = False):
    """q: [B, T, H, hd]; k/v: [B, S, KV, hd] -> [B, T, H, hd].

    Compiles to a Mosaic kernel for the TPU. ``interpret=True`` runs the
    kernel body in the Pallas interpreter instead (CPU tests only).
    """
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    group = H // KV
    scale = 1.0 / math.sqrt(hd)

    block_q = min(block_q, T)
    block_k = min(block_k, S)
    assert T % block_q == 0 and S % block_k == 0, (T, S, block_q, block_k)
    nq, nk = T // block_q, S // block_k

    # layout: [B, H, T, hd] — contiguous per (batch, head) program
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, q_offset=q_offset,
        scale=scale, block_q=block_q, block_k=block_k, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
