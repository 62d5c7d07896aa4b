"""Serving launcher CLI: continuous batching over synthetic requests.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --requests 16 [--slots 4] [--reduced]

Serves the config at its published widths in its own ``param_dtype``;
``--reduced`` swaps in the tiny CPU smoke config in float32.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serve.engine import ServeEngine


def main(argv=None):
    """Runs the CLI; returns the ServeEngine (its ``done`` holds the
    finished requests)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config in float32 (CPU smoke)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), param_dtype="float32",
                                  remat="none")
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    eng = ServeEngine(cfg, params, slots=args.slots, max_seq=args.max_seq)
    rng = np.random.default_rng(args.seed)
    t0 = time.monotonic()
    for _ in range(args.requests):
        plen = int(rng.integers(3, args.max_seq // 4))
        eng.submit(rng.integers(0, cfg.vocab_size, plen),
                   max_new=args.max_new)
    done = eng.run()
    dt = time.monotonic() - t0
    tokens = sum(len(r.tokens) for r in done.values())
    print(f"served {len(done)} requests / {tokens} tokens in {dt:.2f}s "
          f"({tokens/dt:.1f} tok/s, {eng.stats['decode_steps']} ticks)")
    return eng


if __name__ == "__main__":
    main()
