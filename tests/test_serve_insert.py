"""The engine's in-place insert: ``serve_insert`` writes an admitted
request's cache into its slot of the donated batched cache."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import init_cache, init_params
from repro.serve.engine import ServeEngine, make_insert_fn

ARCHS = ["qwen3_0_6b", "zamba2_2_7b", "xlstm_1_3b"]
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def cfg_for(arch):
    """The tiny configs ``test_serve.py`` serves."""
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              param_dtype="float32", remat="none")
    if arch == "qwen3_0_6b":
        cfg = dataclasses.replace(
            cfg, n_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
            head_dim=32, d_ff=128, vocab_size=64, block_pattern=())
    return cfg


def eager_insert(cache, slot_cache, slot):
    """The reference: the same update, run eagerly with nothing donated."""
    return jax.tree_util.tree_map(
        lambda big, one: jax.lax.dynamic_update_slice_in_dim(
            big, one.astype(big.dtype), slot, axis=1), cache, slot_cache)


def engine(arch, slots=4, max_seq=32):
    cfg = cfg_for(arch)
    return ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                       slots=slots, max_seq=max_seq)


@pytest.mark.parametrize("arch", ARCHS)
def test_insert_matches_the_eager_update_in_every_slot(arch):
    eng = engine(arch)
    # random contents, so a write into the wrong slot or rows shows
    leaves, tree = jax.tree_util.tree_flatten(eng.cache)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    eng.cache = jax.tree_util.tree_unflatten(tree, [
        jax.random.normal(k, x.shape).astype(x.dtype)
        for k, x in zip(keys, leaves)])
    for slot in range(eng.slots):
        prompt = jnp.arange(3 + slot, dtype=jnp.int32)[None] % 7 + slot
        _, c1 = eng._prefill_fn(prompt.shape[1])(eng.params, prompt)
        want = eager_insert(eng.cache, c1, slot)
        eng.cache = eng._insert(eng.cache, c1, np.int32(slot))
        for got, ref in zip(jax.tree_util.tree_leaves(eng.cache),
                            jax.tree_util.tree_leaves(want)):
            assert got.dtype == ref.dtype
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("arch", ARCHS)
def test_admission_donates_the_old_cache(arch):
    eng = engine(arch)
    old = jax.tree_util.tree_leaves(eng.cache)
    for p in ([1, 2, 3], [4, 5]):
        eng.submit(np.asarray(p), max_new=4)
    eng.tick()
    assert all(x.is_deleted() for x in old)
    assert eng.stats["inserts"] == 2
    assert eng.stats["inserts_in_place"] == eng.stats["inserts"]
    assert not any(x.is_deleted()
                   for x in jax.tree_util.tree_leaves(eng.cache))


def test_one_insert_program_serves_every_slot():
    lowered = []

    def on_event(event, duration, **kw):
        if event == LOWER_EVENT and kw.get("fun_name") == "jit(serve_insert)":
            lowered.append(kw)

    eng = engine("qwen3_0_6b")
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        # four prompt lengths, one per slot
        for n in range(eng.slots):
            eng.submit(np.arange(2 + 3 * n) % 60, max_new=8)
        eng.tick()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert sum(r is not None for r in eng.active) == eng.slots
    assert eng.stats["inserts"] == eng.slots
    assert len(lowered) == 1


def test_insert_program_is_named():
    cfg = cfg_for("qwen3_0_6b")
    big = jax.eval_shape(lambda: init_cache(cfg, 4, 64))
    one = jax.eval_shape(lambda: init_cache(cfg, 1, 64))
    low = make_insert_fn().lower(big, one,
                                 jax.ShapeDtypeStruct((), jnp.int32))
    assert "module @jit_serve_insert " in low.as_text()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_init_cache_leaves_are_distinct_buffers(arch):
    """Donating the cache would fail on a buffer that two leaves share."""
    cfg = get_config(arch).reduced()
    if cfg.mla:
        with pytest.raises(NotImplementedError, match="latent cache"):
            init_cache(cfg, 4, 32)
        return
    leaves = jax.tree_util.tree_leaves(init_cache(cfg, 4, 32))
    ptrs = {x.unsafe_buffer_pointer() for x in leaves}
    assert len(ptrs) == len(leaves)
