"""Sharding plans: structural validity over every arch × mesh shape.

Uses AbstractMesh (no devices needed) to validate that every PartitionSpec
in the plan (a) matches the parameter/cache tree structurally and (b) only
shards dimensions that are divisible by the assigned axes — the invariant
that makes the 512-chip dry-run compile.
"""
from __future__ import annotations

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES
from repro.models import abstract_params, init_cache
from repro.parallel import (batch_specs, cache_specs, make_plan, param_specs,
                            token_spec)

MESHES = [
    AbstractMesh((16, 16), ("data", "model")),          # production single
    AbstractMesh((2, 16, 16), ("pod", "data", "model")),  # production multi
    AbstractMesh((4, 8), ("data", "model")),            # odd ratio
    AbstractMesh((1, 4), ("data", "model")),            # TP-only
    AbstractMesh((8, 1), ("data", "model")),            # DP-only
]


def _axes_of(spec_entry):
    if spec_entry is None:
        return ()
    if isinstance(spec_entry, str):
        return (spec_entry,)
    return tuple(spec_entry)


def assert_spec_divides(tree, spec_tree, mesh, what):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    specs = treedef.flatten_up_to(spec_tree)
    assert len(leaves) == len(specs)
    for leaf, spec in zip(leaves, specs):
        assert isinstance(spec, P), (what, spec)
        assert len(spec) <= leaf.ndim, (what, leaf.shape, spec)
        for dim, entry in zip(leaf.shape, spec):
            total = 1
            for ax in _axes_of(entry):
                assert ax in mesh.shape, (what, ax)
                total *= mesh.shape[ax]
            assert dim % total == 0, (what, leaf.shape, spec)
        # no axis used twice within one spec
        used = [a for e in spec for a in _axes_of(e)]
        assert len(used) == len(set(used)), (what, spec)


@pytest.mark.parametrize("mesh", MESHES, ids=lambda m: "x".join(
    map(str, m.shape.values())))
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_valid(arch, mesh):
    cfg = get_config(arch)
    params = abstract_params(cfg)
    specs = param_specs(cfg, mesh)
    assert_spec_divides(params, specs, mesh, f"{arch} params")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_state_fits_hbm_budget(arch):
    """The production invariant: per-device bytes for params + grads + AdamW
    moments (given each leaf's sharding) must fit a v5e HBM budget slice.
    Small archs intentionally replicate attention weights (fsdp=False keeps
    weight collectives at zero); this test is what bounds that choice."""
    mesh = MESHES[0]
    cfg = get_config(arch)
    params = abstract_params(cfg)
    specs = param_specs(cfg, mesh)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    sflat = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    opt_bytes = 4 if cfg.opt_state_dtype == "float32" else 2
    per_device = 0.0
    for (path, leaf), spec in zip(flat, sflat):
        n = 1
        for s in leaf.shape:
            n *= s
        shards = 1
        for e in spec:
            for ax in _axes_of(e):
                shards *= mesh.shape[ax]
        # persistent state: param (bf16) + AdamW m + v (transient grads /
        # activations are bounded separately via the dry-run memory table)
        per_device += n / shards * (2 + 2 * opt_bytes)
    budget = 12 * 2**30                  # 12 GiB of the 16 GiB HBM for state
    assert per_device < budget, (arch, per_device / 2**30)


@pytest.mark.parametrize("mesh", MESHES[:3], ids=["16x16", "2x16x16", "4x8"])
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_batch_and_cache_specs_valid(arch, mesh):
    cfg = get_config(arch)
    plan = make_plan(cfg, mesh)
    for shape_name in ("train_4k", "decode_32k"):
        shape = SHAPES[shape_name]
        bsp = batch_specs(cfg, mesh, shape.kind, plan,
                          batch=shape.global_batch)
        assert "tokens" in bsp
        if cfg.mla:
            with pytest.raises(NotImplementedError, match="latent cache"):
                init_cache(cfg, shape.global_batch, shape.seq_len)
            continue
        cache = jax.eval_shape(
            lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
        csp = cache_specs(cfg, mesh, plan, batch=shape.global_batch,
                          seq_len=shape.seq_len)
        assert_spec_divides(cache, csp, mesh, f"{arch} cache {shape_name}")
        tsp = token_spec(shape.global_batch, mesh, plan)
        assert isinstance(tsp, P)


def test_plan_policy_matrix():
    mesh = MESHES[0]                                   # model axis = 16
    plans = {a: make_plan(get_config(a), mesh) for a in ARCH_IDS}
    # head-TP only where heads % 16 == 0
    assert not plans["qwen3_14b"].tp_heads        # 40 % 16 != 0 -> context par
    assert plans["qwen3_14b"].context_parallel
    assert plans["nemotron_4_340b"].tp_heads      # 96 % 16 == 0
    assert plans["mixtral_8x22b"].tp_heads        # 48 % 16 == 0
    assert not plans["qwen2_1_5b"].tp_heads       # 12 % 16 != 0
    assert plans["qwen2_1_5b"].context_parallel
    # EP only where experts % 16 == 0
    assert not plans["mixtral_8x22b"].ep          # 8 experts < 16
    assert plans["moonshot_v1_16b_a3b"].ep        # 64 % 16 == 0
    # vocab TP where divisible
    assert plans["qwen3_14b"].vocab_tp            # 151936 % 16 == 0
    assert plans["nemotron_4_340b"].vocab_tp      # 256000 % 16 == 0


def test_plan_qwen3_14b_heads():
    """40 heads on a 16-wide model axis: context parallelism, not head-TP."""
    mesh = MESHES[0]
    plan = make_plan(get_config("qwen3_14b"), mesh)
    assert plan.tp_heads == (40 % 16 == 0)


def test_multi_pod_folds_pod_into_dp():
    mesh = MESHES[1]
    plan = make_plan(get_config("qwen3_0_6b"), mesh)
    assert plan.dp == ("pod", "data")
    assert plan.dp_total == 32


def test_fsdp_flag_respected():
    import dataclasses
    mesh = MESHES[0]
    cfg = get_config("qwen3_14b")
    on = param_specs(cfg, mesh)
    off = param_specs(dataclasses.replace(cfg, fsdp=False), mesh)
    flat_on = jax.tree_util.tree_flatten(
        on, is_leaf=lambda x: isinstance(x, P))[0]
    flat_off = jax.tree_util.tree_flatten(
        off, is_leaf=lambda x: isinstance(x, P))[0]
    n_data_on = sum(1 for s in flat_on
                    for e in s for a in _axes_of(e) if a == "data")
    n_data_off = sum(1 for s in flat_off
                     for e in s for a in _axes_of(e) if a == "data")
    assert n_data_on > n_data_off == 0
