"""A run with the timed path broken underneath reads ``correct`` false.

Each test skips the harness's look for a chip and drives the rest of a run
at a tiny size on the CPU, against the cells' own limits, with one fault
planted in the program: a train step that returns its state unchanged;
half of the batch left out, the mean taken over the rest; every step at
0.7 of the learning rate; a served token altered where the engine
produces it. The last test puts the fp8 control
in the program's place and sees it fail too.
"""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from chipbench import check, control, drive_serve, drive_train
from chipbench.run import CompileCounter, Tracer
from chipbench.test_chipbench_harness import SERVE_TINY, run_tiny, tiny_cell


def _train_cell():
    return tiny_cell("qwen3-0.6b.train", seq_len=256)


def _break_step(monkeypatch, wrap):
    import repro.train.trainer as trainer_mod
    make = trainer_mod.make_train_step

    def broken(cfg, mesh, **kw):
        fn, in_sh, out_sh = make(cfg, mesh, **kw)
        return wrap(fn), in_sh, out_sh

    monkeypatch.setattr(trainer_mod, "make_train_step", broken)


def test_sound_train_run_is_correct():
    out = run_tiny(_train_cell())
    assert out["correct"] is True, out["checks"]


def test_step_that_returns_its_state_unchanged(monkeypatch):
    def wrap(fn):
        def step(params, opt, batch, i):
            _, _, metrics = fn(params, opt, batch, i)
            return params, opt, metrics
        return step
    _break_step(monkeypatch, wrap)
    out = run_tiny(_train_cell())
    assert out["correct"] is False
    assert out["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(monkeypatch):
    def wrap(fn):
        def step(params, opt, batch, i):
            half = {k: jnp.concatenate([v[: v.shape[0] // 2]] * 2)
                    for k, v in batch.items()}
            return fn(params, opt, half, i)
        return step
    _break_step(monkeypatch, wrap)
    out = run_tiny(_train_cell())
    assert out["correct"] is False, out["checks"]


def test_step_at_a_lower_learning_rate(monkeypatch):
    import repro.train.step as step_mod
    update = step_mod.adamw_update

    def slow(grads, opt_state, params, *, lr, **kw):
        return update(grads, opt_state, params, lr=lr * control.LR_FAULT,
                      **kw)
    monkeypatch.setattr(step_mod, "adamw_update", slow)
    out = run_tiny(_train_cell())
    assert out["correct"] is False, out["checks"]


def _chat_cell():
    return tiny_cell("qwen3-0.6b.chat", rate_per_s=20.0, **SERVE_TINY)


def test_sound_serve_run_is_correct():
    out = run_tiny(_chat_cell(), seconds=1.0)
    assert out["correct"] is True, out["checks"]


def test_token_altered_where_produced(monkeypatch):
    import repro.serve.engine as engine_mod
    make = engine_mod.make_decode_fn

    def broken(cfg):
        fn = make(cfg)

        def decode(params, token, cache, cache_len):
            logits, cache = fn(params, token, cache, cache_len)
            # slot 0's pick moves to the next token id
            return logits.at[0].set(jnp.roll(logits[0], 1)), cache
        return decode
    monkeypatch.setattr(engine_mod, "make_decode_fn", broken)
    out = run_tiny(_chat_cell(), seconds=1.0)
    assert out["correct"] is False, out["checks"]


def test_fp8_control_fails_the_limits():
    from chipbench.record import Run
    cell = _train_cell()
    run = Run(cell, 2**31 + 17, 1, None)
    ref = drive_train.reference_readings(run)
    low = drive_train.reference_readings(run, "fp8")
    ok, _ = check.judge(check.train_numbers(low, ref), cell.limits)
    assert not ok
    half = drive_train.reference_readings(run, transform=control.half_batch)
    ok, _ = check.judge(check.train_numbers(half, ref), cell.limits)
    assert not ok
    slow = drive_train.reference_readings(run, lr_scale=control.LR_FAULT)
    ok, _ = check.judge(check.train_numbers(slow, ref), cell.limits)
    assert not ok


def test_fp8_control_fails_the_serve_limit():
    from chipbench.record import Run
    cell = _chat_cell()
    run = Run(cell, 2**31 + 17, 1, None)
    readings, *_ = drive_serve.drive(run, 1.0, Tracer(run, False),
                                     CompileCounter(), 0.0, exact=False)
    nums = drive_serve.numbers(run, readings, control=True)
    assert nums["control_gap"] > cell.limits["served_gap"]
