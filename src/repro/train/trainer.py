"""Fault-tolerant training loop.

Production behaviours, testable single-host:
  * periodic async checkpointing (CheckpointManager)
  * resume-from-latest on construction (elastic: any mesh)
  * preemption handling — SIGTERM/SIGINT trigger checkpoint-then-exit
  * step retry with bounded backoff on transient failures (the single-host
    analogue of "respawn the task on another node"; the scheduler-level
    re-dispatch lives in repro.core)
  * deterministic data by step index -> no data loss/duplication across
    restarts.

Each part of a step (batch, dispatch, sync) and each checkpoint save is a
``jax.profiler.TraceAnnotation`` named ``repro.train.*`` with the step as an
argument, so a profiler trace of a running trainer places them beside the
device's work (README, "Tracing"). A model with experts fetches each
step's routed rows with its loss and writes them in ``repro.train.moe``
after the sync: ``tokens`` of the batch, ``rows`` summed over the MoE blocks
and ``rows_max``, the busiest held expert of any block.
"""
from __future__ import annotations

import signal
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.ckpt import CheckpointManager, latest_step, restore
from repro.configs.base import ArchConfig
from repro.train.step import init_train_state, make_train_step


@dataclass
class TrainerConfig:
    ckpt_dir: str            # resumed from if it holds a checkpoint
    ckpt_every: int = 50
    keep: int = 3
    max_retries: int = 3
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 1000
    log_every: int = 10


class Trainer:
    def __init__(self, cfg: ArchConfig, mesh: Mesh, batch_fn: Callable,
                 tc: TrainerConfig, log: Callable[[str], None] = print):
        self.cfg, self.mesh, self.tc = cfg, mesh, tc
        self.batch_fn = batch_fn
        self.log = log
        self.mgr = CheckpointManager(tc.ckpt_dir, keep=tc.keep)
        self._preempted = False

        step_fn, in_sh, out_sh = make_train_step(
            cfg, mesh, peak_lr=tc.peak_lr, warmup=tc.warmup,
            total_steps=tc.total_steps)
        with mesh:
            self.step_fn = jax.jit(
                step_fn,
                in_shardings=jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), in_sh),
                out_shardings=jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), out_sh),
                donate_argnums=(0, 1))

        # ---- init or elastic resume ----------------------------------------
        self.params, self.opt_state = init_train_state(cfg, mesh)
        self.step = 0
        last = latest_step(tc.ckpt_dir)
        if last is not None:
            self._restore(last)

    # ------------------------------------------------------------------
    def _restore(self, step: int):
        from repro.parallel import make_plan, param_specs
        plan = make_plan(self.cfg, self.mesh)
        psp = param_specs(self.cfg, self.mesh, plan)
        sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), psp)
        opt_sh = {"m": sh, "v": sh,
                  "count": NamedSharding(self.mesh, P())}
        state = {"params": self.params, "opt": self.opt_state}
        shardings = {"params": sh, "opt": opt_sh}
        restored, manifest = restore(self.tc.ckpt_dir, state, step=step,
                                     shardings=shardings)
        self.params, self.opt_state = restored["params"], restored["opt"]
        self.step = manifest["step"]
        self.log(f"[trainer] resumed from step {self.step} "
                 f"(mesh {dict(self.mesh.shape)})")

    def _checkpoint(self, blocking=False):
        state = {"params": self.params, "opt": self.opt_state}
        with TraceAnnotation("repro.train.checkpoint", step=self.step,
                             blocking=int(blocking)):
            self.mgr.save_async(self.step, state,
                                meta={"arch": self.cfg.name})
            if blocking:
                self.mgr.wait()

    def _on_preempt(self, signum, frame):
        self._preempted = True

    # ------------------------------------------------------------------
    def run(self, num_steps: int) -> Dict[str, Any]:
        old1 = signal.signal(signal.SIGTERM, self._on_preempt)
        old2 = signal.signal(signal.SIGINT, self._on_preempt)
        losses, step_s = [], []
        t0 = time.monotonic()
        try:
            end = self.step + num_steps
            while self.step < end and not self._preempted:
                t_step = time.monotonic()
                with TraceAnnotation("repro.train.batch", step=self.step):
                    batch = self.batch_fn(self.step)
                    batch = {k: jax.numpy.asarray(v)
                             for k, v in batch.items()}
                with TraceAnnotation("repro.train.dispatch",
                                     step=self.step) as span:
                    for attempt in range(self.tc.max_retries + 1):
                        try:
                            self.params, self.opt_state, metrics = \
                                self.step_fn(self.params, self.opt_state,
                                             batch, jax.numpy.int32(self.step))
                            break
                        except Exception as e:  # transient failure -> retry
                            if attempt == self.tc.max_retries:
                                self._checkpoint(blocking=True)
                                raise
                            self.log(f"[trainer] step {self.step} failed "
                                     f"({type(e).__name__}: {e}); "
                                     f"retry {attempt+1}")
                            time.sleep(0.1 * 2 ** attempt)
                    span.set_metadata(attempt=attempt)
                self.step += 1
                with TraceAnnotation("repro.train.sync", step=self.step - 1):
                    # waits for the step
                    loss, rows = jax.device_get(
                        (metrics["loss"], metrics.get("expert_rows")))
                    loss = float(loss)
                if rows is not None:
                    with TraceAnnotation(
                            "repro.train.moe", step=self.step - 1,
                            tokens=int(batch["tokens"].size),
                            rows=int(rows.sum()), rows_max=int(rows.max())):
                        pass
                losses.append(loss)
                step_s.append(time.monotonic() - t_step)
                if self.step % self.tc.log_every == 0:
                    dt = time.monotonic() - t0
                    self.log(f"[trainer] step {self.step} loss {loss:.4f} "
                             f"({dt:.1f}s)")
                if self.step % self.tc.ckpt_every == 0:
                    self._checkpoint()
            if self._preempted:
                self.log("[trainer] preemption signal — checkpointing")
                self._checkpoint(blocking=True)
        finally:
            signal.signal(signal.SIGTERM, old1)
            signal.signal(signal.SIGINT, old2)
            self.mgr.wait()
        return {"losses": losses, "step_s": step_s, "step": self.step,
                "preempted": self._preempted}
