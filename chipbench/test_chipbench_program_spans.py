"""The program's spans and program names as the reduction reads them: a
reduced ServeEngine and Trainer run under the profiler on the CPU."""
from __future__ import annotations

import dataclasses
import glob
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program_trace as pt


def tiny_cfg():
    from repro.configs import get_config
    return dataclasses.replace(
        get_config("qwen3_0_6b").reduced(),
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=64, block_pattern=(), remat="none",
        param_dtype="float32")


def traced(tmp_path, fn):
    """The program spans that ``fn()`` writes, by name (in time order)."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = defaultdict(list)
    for name, s, e, args in sorted(pt.load(path[0]).spans,
                                   key=lambda sp: sp[1]):
        spans[name].append((s, e, args))
    return spans


def test_engine_spans_carry_their_counters(tmp_path):
    from repro.models import init_params
    from repro.serve.engine import ServeEngine
    cfg = tiny_cfg()
    eng = ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                      slots=2, max_seq=64)
    prompts = [[1, 2, 3], [10, 20, 30, 40, 5], [7], [9, 9, 9, 9]]
    rids = [eng.submit(np.asarray(p), max_new=4) for p in prompts]
    in_use = []                     # slots in use at each decode dispatch
    decode = eng._decode

    def counting(params, token, cache, cache_len):
        in_use.append(int((np.asarray(cache_len) > 0).sum()))
        return decode(params, token, cache, cache_len)

    eng._decode = counting
    spans = traced(tmp_path, eng.run)

    admits = spans["serve.admit"]
    assert [a["rid"] for _, _, a in admits] == rids
    assert [a["length"] for _, _, a in admits] == [len(p) for p in prompts]
    assert all(a["wait_us"] >= 0 for _, _, a in admits)
    by_rid = {a["rid"]: (s, e) for s, e, a in admits}
    # every request reaches a slot (none ends at its first token)
    for child in ("serve.prefill", "serve.insert"):
        assert sorted(a["rid"] for _, _, a in spans[child]) == rids
        for s, e, a in spans[child]:
            lo, hi = by_rid[a["rid"]]
            assert lo <= s and e <= hi, (child, a)
    assert {a["slot"] for _, _, a in spans["serve.insert"]} == {0, 1}
    decodes = spans["serve.decode"]
    assert len(decodes) == eng.stats["decode_steps"] == len(in_use)
    assert [a["active"] for _, _, a in decodes] == in_use
    assert {a["slots"] for _, _, a in decodes} == {2}
    samples = spans["serve.sample"]
    assert len(samples) == len(decodes)
    assert sum(a["finished"] for _, _, a in samples) == len(prompts)


def test_trainer_spans_carry_the_step(tmp_path):
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import Trainer, TrainerConfig
    cfg = tiny_cfg()
    src = SyntheticLM(cfg.vocab_size, 16, 4, seed=0)
    trainer = Trainer(cfg, make_host_mesh(1, 1), src.batch, TrainerConfig(
        ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2, log_every=1000),
        log=lambda s: None)
    spans = traced(tmp_path / "trace", lambda: trainer.run(2))
    for name in ("train.batch", "train.dispatch", "train.sync"):
        assert [a["step"] for _, _, a in spans[name]] == [0, 1], name
    assert [a["attempt"] for _, _, a in spans["train.dispatch"]] == [0, 0]
    assert [a for _, _, a in spans["train.checkpoint"]] == [
        {"step": 2, "blocking": 0}]
    # batch, dispatch, sync follow each other within a step
    for k in range(2):
        b, d, s = (spans[n][k] for n in
                   ("train.batch", "train.dispatch", "train.sync"))
        assert b[1] <= d[0] and d[1] <= s[0]


def test_serve_programs_are_named():
    from repro.models import init_cache, init_params
    from repro.serve.engine import ServeEngine, make_decode_fn, \
        make_prefill_fn
    cfg = tiny_cfg()
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, 5), jnp.int32)
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, 64))
    lowered = {
        "serve_prefill": [
            make_prefill_fn(cfg).lower(params, tokens),
            ServeEngine(cfg, None, slots=2, max_seq=64)._prefill_fn(5)
            .lower(params, tokens)],
        "serve_decode": [make_decode_fn(cfg).lower(
            params, jax.ShapeDtypeStruct((2,), jnp.int32), cache,
            jax.ShapeDtypeStruct((2,), jnp.int32))]}
    for name, programs in lowered.items():
        for low in programs:
            assert f"module @jit_{name} " in low.as_text()
            assert pt.module_name(f"jit_{name}(123)") == name


@pytest.mark.parametrize("blocking", [False, True])
def test_checkpoint_span_says_whether_it_blocks(tmp_path, blocking):
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import Trainer, TrainerConfig
    cfg = tiny_cfg()
    trainer = Trainer(cfg, make_host_mesh(1, 1), None, TrainerConfig(
        ckpt_dir=str(tmp_path / "ckpt")), log=lambda s: None)
    spans = traced(tmp_path / "trace",
                   lambda: trainer._checkpoint(blocking=blocking))
    trainer.mgr.wait()
    assert [a for _, _, a in spans["train.checkpoint"]] == [
        {"step": 0, "blocking": int(blocking)}]


def test_a_cell_run_keeps_its_trace(tmp_path):
    # the script's path through run.py: the profiler starts late, stops
    # after its limit, and the kept trace holds the engine's spans (the
    # CPU has no device plane, so there is nothing to reduce)
    import time
    from chipbench import run as bench_run
    from chipbench.test_chipbench_harness import SERVE_TINY, tiny_cell
    cell = tiny_cell("qwen3-0.6b.chat", rate_per_s=20.0, **SERVE_TINY)
    cell.per_layer = [{"name": "chat.window_compiles", "unit": "programs"}]
    plain = bench_run.Tracer
    out, red = pt.run_cell(cell, 5, 1.0, devices=jax.devices()[:1],
                           peaks=None, t_start=time.monotonic(),
                           keep=str(tmp_path), delay_s=0.3, limit_s=0.3,
                           exact=False)
    assert bench_run.Tracer is plain
    assert out["correct"] is True, out["checks"]
    assert red is None
    kept = glob.glob(f"{tmp_path}/*.xplane.pb")
    assert len(kept) == 1
    t = pt.load(kept[0])
    names = {n for n, _, _, _ in t.spans}
    assert {"serve.decode", "serve.sample"} <= names
    window = [(s, e) for n, s, e in t.base.spans if n == "window"]
    assert len(window) == 1
    assert 0.25e9 < window[0][1] - window[0][0] < 0.6e9
