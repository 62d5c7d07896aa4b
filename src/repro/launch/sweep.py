"""The end-to-end "interactive supercomputing" driver (the paper as a CLI).

    PYTHONPATH=src python -m repro.launch.sweep --arch qwen3-0.6b \
        --members 16 --steps 5

Workflow (mirrors §III/§IV on a TPU-style runtime):
  1. PREPOSITION (slow path, before the analyst is waiting): compile the
     member-step executable and materialize base weights — the analogue of
     copying the MATLAB installs to every node's local disk.
  2. INTERACTIVE LAUNCH: submit the sweep as ONE repro.taskarray job array
     (the LLMapReduce shape) whose tasks each stamp a member through the
     warm cache under a chip quota; the gather layer reports per-member
     status, retries, and the aggregate launch rate, exactly the way
     Fig. 4 reports process-launch times.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import SHAPES
from repro.core.supervisor import SweepSupervisor
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import abstract_params, forward_loss, init_params
from repro.optim import adamw_init, adamw_update
from repro.parallel import param_specs
from repro.exec import get_backend
from repro.taskarray import RetryPolicy, TaskGraph


def build_member_step(cfg, mesh):
    psp = param_specs(cfg, mesh)
    opt_spec = {"m": psp, "v": psp, "count": P()}

    def member_step(params, opt, batch, lr):
        (loss, _), grads = jax.value_and_grad(
            lambda p: forward_loss(p, cfg, batch), has_aux=True)(params)
        params, opt, _ = adamw_update(grads, opt, params, lr=lr)
        return params, opt, loss

    params_abs = abstract_params(cfg)
    opt_abs = jax.eval_shape(lambda: adamw_init(params_abs, "float32"))
    batch_abs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
    bsp = {"tokens": P(), "labels": P()}
    return member_step, (psp, opt_spec, bsp, P()), (psp, opt_spec, P()), (
        params_abs, opt_abs, batch_abs, jax.ShapeDtypeStruct((), jnp.float32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--members", type=int, default=16)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--max-chips", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = dataclasses.replace(get_config(args.arch).reduced(),
                              n_layers=2, param_dtype="float32",
                              remat="none")
    mesh = make_host_mesh(1, 1)
    shape = SHAPES["train_4k"]
    sup = SweepSupervisor(max_chips=args.max_chips)

    t0 = time.monotonic()
    sup.preposition(cfg, shape, mesh, lambda: build_member_step(cfg, mesh),
                    init=lambda: init_params(cfg, jax.random.PRNGKey(0)))
    print(f"prepositioned in {time.monotonic() - t0:.2f}s")

    src = SyntheticLM(cfg.vocab_size, 32, 8, seed=0)
    base_params = sup.weights.get(cfg, mesh, 0)
    grid = [{"lr": float(lr)}
            for lr in np.geomspace(1e-4, 3e-2, args.members)]

    def run_member(entry, member):
        params, opt = base_params, adamw_init(base_params, "float32")
        loss = None
        for step in range(args.steps):
            b = {k: jnp.asarray(v) for k, v in src.batch(step).items()}
            params, opt, loss = entry.compiled(
                params, opt, b, jnp.float32(member.hparams["lr"]))
        return float(loss)

    # the sweep IS a task array: one task per member, submitted through
    # the unified exec backend layer (repro.exec) and gathered with
    # per-task status/retries and an array-level launch summary
    def member_fn(params, inputs):
        [m] = sup.launch_sweep(cfg, shape, mesh, [params], run_member)
        if m.state == "held":
            raise RuntimeError("held: over chip quota")
        sup.release(m)          # steps done -> member's lifetime ends
        return {"lr": params["lr"], "loss": m.result,
                "launch_s": m.launch_time}

    graph = TaskGraph("hparam-sweep")
    graph.map(member_fn, grid, name="sweep")
    t0 = time.monotonic()
    backend = get_backend("inline")
    res = graph.run(backend, RetryPolicy(max_retries=0))
    arr = res["sweep"]
    dt = time.monotonic() - t0
    ran = [v for v in arr.values if v is not None]
    best = min(ran, key=lambda v: v["loss"]) if ran else None
    print(f"launched {len(ran)}/{arr.summary.n_tasks} members x "
          f"{args.steps} steps in {dt:.2f}s "
          f"({len(ran)/max(dt,1e-9):.1f}/s; {arr.summary.failed} held "
          f"by quota; compiles in loop: {sup.warmer.stats['warms'] - 1 if sup.warmer.stats['warms'] > 1 else 0})")
    if best:
        print(f"best member: lr={best['lr']:.2e} "
              f"loss={best['loss']:.4f} launch={1e3*best['launch_s']:.0f}ms")
    print(f"array: {arr.summary}")
    print(f"events: {res.events.counts()}")
    print(f"report: {sup.launch_report()}")


if __name__ == "__main__":
    main()
