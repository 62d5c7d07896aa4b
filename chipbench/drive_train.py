"""Drives the program's Trainer for traffic of kind ``train``.

Set-up builds one Trainer (its jitted step and state) on the traffic's
mesh, puts the benchmark's weights in it, and runs the checked steps
through ``Trainer.run`` and the program's synthetic data pipeline, the
window's own call and feed. The window then goes on with the same object.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

from chipbench import check, flops, reference, spec, traffic_gen, weights


def _delete(tree):
    for leaf in jax.tree_util.tree_leaves(tree):
        leaf.delete()


def drive(run, seconds, tracer, compiles, t_start, exact=True):
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import make_batch_fn
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import Trainer, TrainerConfig

    c, tr = run.cell.config, run.cell.traffic
    cfg = spec.arch_config(c, exact)
    B, T, k = tr["global_batch"], tr["seq_len"], tr["checked_steps"]
    mesh = make_host_mesh(tr["mesh"]["data"], tr["mesh"]["model"])
    source = make_batch_fn(cfg, ShapeConfig("chipbench", T, B, "train"),
                           seed=run.seed)

    def batch_fn(step):
        with run.span("batch"):
            return source(step)

    ckpt = tempfile.mkdtemp(prefix="chipbench-ckpt-")
    try:
        trainer = Trainer(cfg, mesh, batch_fn, TrainerConfig(
            ckpt_dir=ckpt, ckpt_every=10**9, log_every=10**9,
            peak_lr=tr["peak_lr"], warmup=tr["warmup"],
            total_steps=tr["total_steps"]), log=lambda s: None)
        arch, key = spec.arch(c), weights.seed_key(run.seed)
        shard = arch.from_program(
            jax.tree.map(lambda a: a.sharding, trainer.params))
        _delete(trainer.params)
        make = weights.maker(c, shard)
        trainer.params = arch.to_program(make(key))

        # the steps the reference follows
        losses = trainer.run(1)["losses"]
        b1 = tr["optimizer"]["b1"]
        grad = jax.jit(lambda m: weights.leaf_norms(
            {n: x.astype(jnp.float32) / (1 - b1) for n, x in m.items()},
            c))(arch.from_program(trainer.opt_state["m"]))
        losses += trainer.run(k - 1)["losses"]
        change = jax.jit(lambda p, key: weights.leaf_norms(
            {n: p[n].astype(jnp.float32) - w.astype(jnp.float32)
             for n, w in weights.make(c, key).items()}, c))(
            arch.from_program(trainer.params), key)
        readings = {"losses": losses,
                    "grad_norms": weights.flat_norms(jax.device_get(grad), c),
                    "change_norms": weights.flat_norms(
                        jax.device_get(change), c)}

        step_flops = flops.train_step(c, B, T)
        compiles.active = True
        tracer.start()
        t0 = t = time.monotonic()
        run.setup_s = t0 - t_start
        steps = 0
        while t - t0 < seconds:
            with run.span("step"):
                trainer.run(1)
            t = time.monotonic()
            steps += 1
            run.work.append((t, step_flops))
            tracer.after()
        tracer.stop()
        compiles.active = False
        run.window = (t0, t)
        run.counters["window_tokens"] = steps * B * T
        run.counters["window_compiles"] = compiles.n
        memory = max_memory(mesh.devices.flat)
        _delete((trainer.params, trainer.opt_state))
        del trainer
        gc.collect()
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    return readings, steps, 0, memory


def max_memory(devices) -> int:
    """The fullest chip's peak: buffers (``peak_bytes_in_use``) plus the
    scratch that the TPU runtime reserves for a running program's
    temporaries (``peak_bytes_reserved``), which buffers leave out."""
    peaks = []
    for d in devices:
        s = d.memory_stats() or {}
        peaks.append(s.get("peak_bytes_in_use", 0)
                     + s.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def numbers(run, readings, control=False):
    """The check's numbers for the program's readings against the float32
    reference; with ``control``, also those of the fp8 control (put in the
    program's place) under ``control_<name>``."""
    out = check.train_numbers(readings, reference_readings(run))
    if control:
        low = check.train_numbers(reference_readings(run, "fp8"),
                                  reference_readings(run))
        out.update({f"control_{k}": v for k, v in low.items()})
    return out


def reference_readings(run, mode="f32", transform=None, lr_scale=1.0):
    """The reference's losses and norms over the checked steps
    (``transform`` alters the batches and ``lr_scale`` the learning rate,
    to plant a fault)."""
    c, tr = run.cell.config, run.cell.traffic
    tr = {**tr, "peak_lr": tr["peak_lr"] * lr_scale}
    B, T = tr["global_batch"], tr["seq_len"]
    w0 = weights.maker(c)(weights.seed_key(run.seed))
    batches = [traffic_gen.train_batch(run.seed, s, B, T, c["vocab_size"])
               for s in range(tr["checked_steps"])]
    if transform:
        batches = transform(batches)
    with jax.default_matmul_precision("highest"):
        ref = reference.train(w0, c, batches, tr, mode)
    return {"losses": ref["losses"],
            "grad_norms": weights.flat_norms(ref["grad_norms"], c),
            "change_norms": weights.flat_norms(ref["change_norms"], c)}
