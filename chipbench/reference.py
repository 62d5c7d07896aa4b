"""Plain float32 reference of a decoder language model.

Written from the published descriptions, not from the program: it imports
nothing of ``repro``. The decoder layers of each family are in its arch
module (``chipbench/archs/<bench_arch>.py``, ``hidden``), built from the
pieces here that arch modules import: ``rms`` (RMSNorm), ``rope`` (rotary
embeddings on halves, rotate_half), ``attention`` (causal, heads grouped)
and ``einsum`` (a product in the reference's precision). Here too: the LM
head (tied or not), the next-token loss and AdamW with global-norm
clipping. Every matrix product runs at ``Precision.HIGHEST``, so the chip
computes it in full float32.

``mode="fp8"`` is the control: the same arithmetic with each product's
operands rounded to float8 under a per-tensor scale (e4m3 forward, e5m2
for gradients, as fp8 training does), the precision step below the
bfloat16 that the configurations state.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec
from chipbench.weights import leaf_norms, rounded

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512        # query rows per attention block
LOSS_BLOCK = 512     # positions per block of the LM head and loss


def _round(x, exponent_bits, mantissa_bits, top):
    """x rounded to a float8 format under a per-tensor scale that maps its
    largest magnitude to ``top``, the format's largest finite value."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return jax.lax.reduce_precision(x * scale, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits) / scale


@jax.custom_vjp
def _fp8(x):
    """fp8 training's rounding: e4m3 forward, e5m2 for the gradient.
    reduce_precision keeps inf in the top binade of e4m3, so its largest
    value is 240 where float8_e4m3fn's is 448; the grid is the same."""
    return _round(x, 4, 3, 240.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round(g, 5, 2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def einsum(mode: str, subscripts: str, a, b):
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(subscripts, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x [B, T, n, hd]; rotates the two halves of each head."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) * 2.0 / x.shape[-1])
    ang = pos.astype(F32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, mode):
    """Causal attention, heads grouped: q [B, T, H, hd], k [B, T, KV, hd],
    v [B, T, KV, vd] -> [B, T, H, vd], scores scaled by 1/sqrt(hd)."""
    B, T, H, hd = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)          # q head h reads kv head h // rep
    v = jnp.repeat(v, rep, axis=2)
    blk = min(Q_BLOCK, T)
    nb = T // blk
    qb = q.reshape(B, nb, blk, H, hd).transpose(1, 0, 2, 3, 4)

    def one(args):
        qi, i = args
        s = einsum(mode, "bqhd,bkhd->bhqk", qi, k) / np.sqrt(hd)
        ok = jnp.arange(T)[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return einsum(mode, "bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one, (qb, jnp.arange(nb)))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, T, H, v.shape[-1])


def head(w, h, mode="f32"):
    """Logits for hidden rows h [..., d]."""
    table = w["embed"] if "lm_head" not in w else w["lm_head"]
    return einsum(mode, "...d,vd->...v", h, table.astype(F32))


def loss(w, c, tokens, mode="f32"):
    """Mean next-token NLL: position t predicts token t+1."""
    B, T = tokens.shape
    h = spec.arch(c).hidden(w, c, tokens, mode)
    target = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    valid = (jnp.arange(T) < T - 1).astype(F32)
    blk = min(LOSS_BLOCK, T)
    nb = T // blk

    @jax.checkpoint
    def one(args):
        hb, tb, vb = args
        logits = head(w, hb, mode)
        nll = (jax.nn.logsumexp(logits, -1)
               - jnp.take_along_axis(logits, tb[..., None], -1)[..., 0])
        return jnp.sum(nll * vb)

    parts = jax.lax.map(one, (
        h.reshape(B, nb, blk, -1).transpose(1, 0, 2, 3),
        target.reshape(B, nb, blk).transpose(1, 0, 2),
        valid.reshape(nb, blk)[:, None, :]))
    return jnp.sum(parts) / (B * (T - 1))


def lr_at(step: int, peak: float, warmup: int, total: int,
          min_ratio: float = 0.1) -> float:
    """Linear warm-up, then cosine decay to min_ratio x peak."""
    if step < warmup:
        return peak * step / max(warmup, 1)
    prog = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return peak * (min_ratio + (1 - min_ratio) * 0.5
                   * (1 + np.cos(np.pi * prog)))


def _adamw_step(c, opt, mode, state, tokens, lr, count):
    """One AdamW step from float32 state (p, m, v); p holds values of the
    configuration's parameter dtype."""
    p, m, v = state
    lval, g = jax.value_and_grad(loss)(p, c, tokens, mode)
    matrices = spec.arch(c).MATRICES
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    g = {k: x * jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gn, 1e-9))
         for k, x in g.items()}
    b1, b2 = opt["b1"], opt["b2"]
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    store = jnp.dtype(c["param_dtype"])
    new_p, new_m, new_v = {}, {}, {}
    for k in p:
        new_m[k] = b1 * m[k] + (1 - b1) * g[k]
        new_v[k] = b2 * v[k] + (1 - b2) * jnp.square(g[k])
        upd = (new_m[k] / c1) / (jnp.sqrt(new_v[k] / c2) + opt["eps"])
        if k in matrices:
            upd = upd + opt["weight_decay"] * p[k]
        new_p[k] = rounded(p[k] - lr * upd, store)
    return (new_p, new_m, new_v), lval, leaf_norms(g, c)


def train(w0: Dict[str, Any], c: Dict[str, Any], batches: Sequence,
          traffic: Dict[str, Any], mode: str = "f32") -> Dict[str, Any]:
    """Follows the program's first len(batches) steps from weights w0.

    Returns each step's loss, the per-leaf norms of the first step's
    gradient as the optimizer gets it (after clipping), and the per-leaf
    norms of the parameters' change over all the steps.
    """
    opt = traffic["optimizer"]
    p0 = {k: x.astype(F32) for k, x in w0.items()}
    state = (p0, jax.tree.map(jnp.zeros_like, p0),
             jax.tree.map(jnp.zeros_like, p0))
    step = jax.jit(functools.partial(_adamw_step, c, opt, mode),
                   donate_argnums=(0,))
    losses, grad_norms = [], None
    for i, tokens in enumerate(batches):
        lr = lr_at(i, traffic["peak_lr"], traffic["warmup"],
                   traffic["total_steps"])
        state, lval, gnorm = step(state, jnp.asarray(tokens),
                                  jnp.float32(lr), jnp.float32(i + 1))
        losses.append(float(lval))
        if grad_norms is None:
            grad_norms = jax.device_get(gnorm)
    change = jax.jit(lambda p, w: leaf_norms(
        {k: p[k] - w[k].astype(F32) for k in p}, c))(state[0], w0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": jax.device_get(change)}


def items(c: Dict[str, Any]) -> tuple:
    """The arch and the sizes its reference reads (its ``REF_KEYS``),
    hashable for a static jit argument."""
    return (("bench_arch", c["bench_arch"]),) + tuple(
        (k, c[k]) for k in spec.arch(c).REF_KEYS)


@functools.partial(jax.jit, static_argnums=(1, 5))
def served_gaps(w, c_items, tokens, pos, served, control: bool):
    """For one sequence [1, S] (prompt, then the served tokens but the
    last, zero-padded): at each row ``pos`` (the position that predicts
    served token ``served``), how far the served token's logit lies below
    the row's best, in units of the row's standard deviation. With
    ``control``, also the gap of the token that the fp8 control puts
    first."""
    c = dict(c_items)
    ref = head(w, spec.arch(c).hidden(w, c, tokens)[0][pos])
    best, spread = jnp.max(ref, -1), jnp.std(ref, -1)

    def gap(tok):
        return (best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]) \
            / spread

    if not control:
        return gap(served), gap(served)
    low = head(w, spec.arch(c).hidden(w, c, tokens, "fp8")[0][pos],
               "fp8")
    return gap(served), gap(jnp.argmax(low, -1))
