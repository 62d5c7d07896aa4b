"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --ckpt-dir CKPT --steps 100 [--reduced]

Trains the config at its published widths on a data-parallel mesh over
every device that exists; ``--reduced`` swaps in the tiny CPU smoke config.
The Trainer provides async checkpointing, preemption handling (SIGTERM ->
checkpoint -> exit), bounded step retry, and resume from the newest
checkpoint in ``--ckpt-dir`` (see repro.train.trainer), which is therefore
required: a run resumes only from the directory it names.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import make_batch_fn
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.train.trainer import Trainer, TrainerConfig


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config for CPU smoke runs")
    ap.add_argument("--seq", type=int, default=None,
                    help="default 2048 (64 with --reduced)")
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch; default 2 per device (8 with "
                         "--reduced)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", required=True,
                    help="checkpoints go here; the run resumes from the "
                         "newest one found")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", default=None, help="packed .bin corpus path")
    return ap.parse_args(argv)


def resolve(args):
    """(cfg, mesh, shape) the run uses: a data-parallel mesh over all
    devices, and the full config unless ``--reduced``."""
    cfg = get_config(args.arch)
    n_dev = len(jax.devices())
    if args.reduced:
        cfg = cfg.reduced()
        seq, batch = args.seq or 64, args.batch or 8
    else:
        seq, batch = args.seq or 2048, args.batch or 2 * n_dev
    mesh = make_host_mesh(n_dev, 1)
    return cfg, mesh, ShapeConfig("cli", seq, batch, "train")


def main(argv=None):
    """Runs the CLI; returns (trainer, Trainer.run's result)."""
    args = parse_args(argv)
    enable_compile_cache()
    cfg, mesh, shape = resolve(args)
    print(f"arch={cfg.name} mesh={dict(mesh.shape)} seq={shape.seq_len} "
          f"batch={shape.global_batch}")

    batch_fn = make_batch_fn(cfg, shape, corpus=args.data)
    tc = TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                       peak_lr=args.lr, total_steps=args.steps)
    trainer = Trainer(cfg, mesh, batch_fn, tc)
    out = trainer.run(args.steps)
    print(f"done at step {out['step']}; last loss {out['losses'][-1]:.4f}"
          f"{' (preempted)' if out['preempted'] else ''}")
    return trainer, out


if __name__ == "__main__":
    main()
