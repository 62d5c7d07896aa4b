"""Sharding plans: per-param PartitionSpecs + batch/cache specs per shape.

Mesh axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
The "pod" axis is folded into the data-parallel group (DP across pods —
gradient all-reduce crosses the pod boundary; everything else is pod-local).

Policy matrix (decided per arch from static divisibility, see DESIGN.md §5):

  weights    TP over 'model' on the head/ff/vocab/expert dim when divisible
             by the axis size; + FSDP (ZeRO-3) over the data axes when
             cfg.fsdp (2-D sharded weights for the big archs).
  train      activations batch-sharded over data axes. Archs whose head
             count doesn't divide the model axis use CONTEXT PARALLELISM in
             attention instead of head-TP (sequence dim over 'model').
  prefill    same as train.
  decode     batch over data; KV cache: kv-heads over 'model' when divisible,
             else cache sequence dim over 'model' (flash-decoding style
             distributed softmax, GSPMD inserts the reductions).
  MoE        expert dim over 'model' when the experts held divide it (EP);
             otherwise d_ff_expert over 'model' (TP inside each expert).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import abstract_params, init_cache


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis(mesh: Mesh) -> str:
    return "model"


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def _div(n: int, k: int) -> bool:
    return n > 0 and k > 0 and n % k == 0


@dataclass(frozen=True)
class ShardingPlan:
    """Static per-arch sharding decisions for one mesh."""
    model_size: int
    dp_total: int
    tp_heads: bool          # q-heads shard over model
    tp_kv_heads: bool       # kv-heads shard over model
    ep: bool                # expert dim shards over model
    vocab_tp: bool
    fsdp: bool
    context_parallel: bool  # seq-shard attention activations (train/prefill)
    dp: Tuple[str, ...]     # data axes


def make_plan(cfg: ArchConfig, mesh: Mesh) -> ShardingPlan:
    m = axis_size(mesh, "model")
    dp = data_axes(mesh)
    dp_total = 1
    for a in dp:
        dp_total *= axis_size(mesh, a)
    tp_heads = _div(cfg.n_heads, m)
    return ShardingPlan(
        model_size=m,
        dp_total=dp_total,
        tp_heads=tp_heads,
        tp_kv_heads=_div(cfg.n_kv_heads, m),
        ep=_div(cfg.n_held, m),
        vocab_tp=_div(cfg.vocab_size, m),
        fsdp=cfg.fsdp,
        context_parallel=not tp_heads,
        dp=dp,
    )


# --------------------------------------------------------------------------
# param specs
# --------------------------------------------------------------------------
_VECTOR_NAMES = ("ln1", "ln2", "ln_x", "final_norm", "enc_norm", "norm",
                 "gn", "ff_ln", "q_norm", "k_norm", "kv_a_norm", "e_bias",
                 "A_log", "D", "dt_bias", "b", "b_i", "b_f")


def param_specs(cfg: ArchConfig, mesh: Mesh,
                plan: Optional[ShardingPlan] = None):
    """PartitionSpec tree matching abstract_params(cfg)."""
    plan = plan or make_plan(cfg, mesh)
    m = "model"
    dp = plan.dp

    def fsdp(dim: int):
        return dp if (plan.fsdp and _div(dim, plan.dp_total)) else None

    def leaf_spec(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        name = keys[-1]
        in_mixer = "mixer" in keys
        stacked = "stages" in keys or "encoder" in keys  # leading L dim
        shape = leaf.shape[1:] if stacked else leaf.shape
        lead = (None,) if stacked else ()

        def spec(*dims):
            assert len(dims) == len(shape), (keys, shape, dims)
            # divisibility sanitizer: drop any axis that doesn't divide its
            # dim (slstm's ff = 8d/3, odd vocab sizes, ...)
            safe = []
            for dim_size, ax in zip(shape, dims):
                if ax is None:
                    safe.append(None)
                    continue
                axes = (ax,) if isinstance(ax, str) else tuple(ax)
                total = 1
                for a in axes:
                    total *= axis_size(mesh, a)
                safe.append(ax if dim_size % total == 0 else None)
            return P(*(lead + tuple(safe)))

        if name in ("embed", "lm_head"):
            return spec(m if plan.vocab_tp else None, fsdp(shape[1]))
        if name in _VECTOR_NAMES:
            return spec(*([None] * len(shape)))

        if not in_mixer:
            # ---- attention ------------------------------------------------
            if name == "wq":
                return spec(fsdp(shape[0]), m if plan.tp_heads else None)
            if name in ("wk", "wv"):
                return spec(fsdp(shape[0]), m if plan.tp_kv_heads else None)
            if name == "wo":
                return spec(m if plan.tp_heads else None, fsdp(shape[1]))
            if name == "wkv_b":               # MLA: latent -> heads
                return spec(None, m if plan.tp_heads else None)
            if name == "bq":
                return spec(m if plan.tp_heads else None)
            if name in ("bk", "bv"):
                return spec(m if plan.tp_kv_heads else None)
            # ---- MLP / MoE --------------------------------------------------
            if name in ("w_up", "w_gate"):
                if len(shape) == 3:           # expert weights [E, d, f]
                    if plan.ep:
                        return spec(m, fsdp(shape[1]), None)
                    return spec(None, fsdp(shape[1]), m)
                return spec(fsdp(shape[0]), m)
            if name == "w_down":
                if len(shape) == 3:           # [E, f, d]
                    if plan.ep:
                        return spec(m, None, fsdp(shape[2]))
                    return spec(None, m, fsdp(shape[2]))
                return spec(m, fsdp(shape[1]))
            if name == "router":
                return spec(None, m if plan.ep else None)
        else:
            # ---- mLSTM 3-D head projections [d_in, nh, dim] ----------------
            if name in ("wq", "wk", "wv") and len(shape) == 3:
                if _div(shape[1], plan.model_size):      # heads over model
                    return spec(fsdp(shape[0]), m, None)
                # few heads (xlstm nh=4 < axis): value dim over model; the
                # SSD state [.., dqk, dv] then shards on dv and the down-proj
                # contraction dim matches (down: (model, fsdp)). q/k stay
                # replicated — every sharded alternative measured worse:
                # FSDP re-gathers them inside the time loops (+4.6 TiB/step)
                # and d_in-/dqk-TP adds ~100-200 GiB of projection psums;
                # the state instead fits via bf16 optimizer moments
                # (EXPERIMENTS.md §Perf xlstm iterations).
                if name == "wv" and _div(shape[2], plan.model_size):
                    return spec(fsdp(shape[0]), None, m)
                return spec(fsdp(shape[0]), None, None)
            # ---- mamba2 mixer ----------------------------------------------
            if name in ("w_z", "w_x", "up_x", "up_z", "wv"):
                return spec(fsdp(shape[0]), m)   # d_in over model
            if name in ("w_B", "w_C", "w_dt", "wq", "wk"):
                return spec(fsdp(shape[0]),
                            m if _div(shape[1], plan.model_size) else None)
            if name in ("conv_w",):
                return spec(m if _div(shape[0], plan.model_size) else None,
                            None)
            if name in ("conv_b",):
                return spec(m if _div(shape[0], plan.model_size) else None)
            if name in ("out_proj", "down"):
                return spec(m, fsdp(shape[1]))
            if name == "w_if":
                return spec(fsdp(shape[0]), None)
            if name in ("ff_up", "ff_gate"):     # slstm FFN
                return spec(fsdp(shape[0]), m)
            if name == "ff_down":
                return spec(m, fsdp(shape[1]))
            if name in ("w_in", "r"):            # slstm core: replicated
                return spec(*([None] * len(shape)))
        return spec(*([None] * len(shape)))      # default: replicate

    return jax.tree_util.tree_map_with_path(leaf_spec, abstract_params(cfg))


# --------------------------------------------------------------------------
# batch / cache specs
# --------------------------------------------------------------------------
def _dp_for(batch: int, plan: ShardingPlan, mesh: Mesh):
    """Data axes the batch dim can shard over (divisibility-aware): the full
    dp group when divisible, the 'data' axis alone as fallback, else
    replicated (long_500k: global_batch=1)."""
    if _div(batch, plan.dp_total):
        return plan.dp
    if "data" in plan.dp and _div(batch, axis_size(mesh, "data")):
        return ("data",)
    return None


def batch_specs(cfg: ArchConfig, mesh: Mesh, kind: str,
                plan: Optional[ShardingPlan] = None,
                batch: Optional[int] = None):
    """Specs for the input batch dict of train/prefill steps."""
    plan = plan or make_plan(cfg, mesh)
    dp = plan.dp if batch is None else _dp_for(batch, plan, mesh)
    s = {"tokens": P(dp, None), "labels": P(dp, None)}
    if cfg.enc_dec:
        s["frames"] = P(dp, None, None)
    if cfg.mrope_sections:
        s["pos3"] = P(None, dp, None)
        s["patch_embeds"] = P(dp, None, None)
        s["patch_pos"] = P(dp, None)
    return s


def cache_specs(cfg: ArchConfig, mesh: Mesh,
                plan: Optional[ShardingPlan] = None,
                batch: int = 8, seq_len: int = 128):
    """Spec tree matching repro.models.init_cache (stacked over layers).

    KV tensors are [L, B, S, KV, hd]: batch over data (when divisible);
    kv-heads over model when divisible, else the cache sequence dim over
    model (flash-decoding-style distributed softmax — GSPMD inserts the
    max/sum all-reduces).
    """
    plan = plan or make_plan(cfg, mesh)
    dp = _dp_for(batch, plan, mesh)
    m = plan.model_size

    def leaf_spec(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        nd = leaf.ndim
        if "kv" in keys or "xkv" in keys:        # [L, B, S, KV, hd]
            if plan.tp_kv_heads:
                return P(None, dp, None, "model", None)
            if _div(leaf.shape[2], m):           # seq over model
                return P(None, dp, "model", None, None)
            return P(None, dp, None, None, None)
        if "conv" in keys:                       # [L, B, K-1, conv_dim]
            ax = "model" if _div(leaf.shape[-1], m) else None
            return P(None, dp, None, ax)
        if "ssm_n" in keys:                      # [L, B, H, N]
            ax = "model" if _div(leaf.shape[2], m) else None
            return P(None, dp, ax, None)
        if "ssm" in keys:                        # [L, B, H, N|dqk, P]
            ax = "model" if _div(leaf.shape[2], m) else None
            return P(None, dp, ax, None, None)
        # slstm scalar states [L, B, d]
        return P(*([None] * (nd - 2)), dp, None)

    cache_shape = jax.eval_shape(lambda: init_cache(cfg, batch, seq_len))
    return jax.tree_util.tree_map_with_path(leaf_spec, cache_shape)


def token_spec(batch: int, mesh: Mesh, plan: Optional[ShardingPlan] = None,
               cfg: Optional[ArchConfig] = None):
    """Spec for the decode-step token vector [B]."""
    assert plan is not None or cfg is not None
    plan = plan or make_plan(cfg, mesh)
    return P(_dp_for(batch, plan, mesh))
