#!/usr/bin/env python3
"""Chip smoke test: qwen3-0.6b trained and served end to end on a TPU.

    python3 chip_smoke.py            # one chip: train phase, serve phase
    python3 chip_smoke.py --chips 4  # four chips: (data=2, model=2) train
                                     # step against the same step on one
                                     # device, and nothing else

Everything runs in this one process, because a chip belongs to one process
at a time. The model runs at its published widths (configs/qwen3_0_6b.py)
with random weights from a fixed seed.

Train phase: ``repro.launch.train.main`` takes a few steps at B=2, T=2048
in a fresh checkpoint directory; every loss must be finite, the first
within 1 nat of ln(vocab), and the parameters must live on the TPU.

Serve phase: ``repro.launch.serve.main`` serves a few requests at full
width. For one finished request, the logits of prefill followed by decode
must match the logits of one full forward pass over the same tokens within
SERVE_LOGIT_TOL (bf16 weights, activations and cache; see below).

Four-chip phase: the same initial state and batches are stepped by the
Trainer on a (data=2, model=2) mesh and on one device; the losses must
agree within FOUR_CHIP_LOSS_TOL and the parameters must be spread over all
four devices.

Lines that start with ``info:`` are informational timings, not results.
The last line of stdout is the JSON result. Without a TPU, or if any check
fails, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
from pathlib import Path

ARCH = "qwen3-0.6b"
# one chip (16 GiB HBM): B=2 x T=2048 is what a full-width train step with
# AdamW state fits (15.75 GiB usable; B=4 leaves no headroom)
TRAIN_BATCH, TRAIN_SEQ = 2, 2048
TRAIN_ARGS = ["--steps", "3", "--batch", str(TRAIN_BATCH),
              "--seq", str(TRAIN_SEQ)]
SERVE_REQUESTS, SERVE_SLOTS, SERVE_MAX_SEQ, SERVE_MAX_NEW = 4, 4, 1024, 8
SERVE_ARGS = ["--requests", str(SERVE_REQUESTS), "--slots", str(SERVE_SLOTS),
              "--max-seq", str(SERVE_MAX_SEQ), "--max-new", str(SERVE_MAX_NEW)]
# |prefill+decode logits - full-forward logits| <= TOL * max |full logits|.
# Both paths run bf16 weights and a bf16 KV cache through 28 layers and sum
# in different orders. Readings on a TPU v5e at full width: 0.042-0.050 on
# the correct path, 0.64 with the decode position off by one. Matmul
# precision "highest" gives the same readings as the default; the same
# 28-layer model at reduced widths reads 0.014 on the TPU and 0.005 on the
# CPU, so the chip's larger error comes from its bf16 arithmetic and from
# width.
SERVE_LOGIT_TOL = 0.1
# a served token's full-forward logit may sit below that row's max by at
# most this x max |logit| (a bf16 tie margin). Readings on a TPU v5e at full
# width: 0-0.015 on the correct path, 0.32 with the decode position off by
# one, 1.16-1.25 for tokens picked from another request's context.
SERVE_ARGMAX_TOL = 0.1
# four chips vs one: the same bf16 step under a different partitioning.
FOUR_CHIP_LOSS_TOL = 2e-2
# B=4 gives each data shard of the (2, 2) mesh the one-chip phase's B=2;
# T=1024 because the one-device reference at B=4, T=2048 needs 15.4 GiB of
# the chip's 15.75
FOUR_CHIP_BATCH, FOUR_CHIP_SEQ, FOUR_CHIP_STEPS = 4, 1024, 3


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL: {what}")


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


def leaf_devices(tree):
    import jax
    return {d for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices()}


def train_phase(extra=()):
    import jax
    import numpy as np
    from repro.launch import train

    with tempfile.TemporaryDirectory() as ckpt:
        trainer, out = train.main(["--arch", ARCH, *TRAIN_ARGS,
                                   "--ckpt-dir", ckpt,
                                   "--ckpt-every", "1000000", *extra])
    losses, step_s = out["losses"], out["step_s"]
    ln_v = math.log(trainer.cfg.vocab_size)
    check(len(losses) == len(step_s) > 1, f"train steps ran: {losses}")
    check(bool(np.isfinite(losses).all()), f"train losses finite: {losses}")
    check(abs(losses[0] - ln_v) < 1.0,
          f"first loss {losses[0]:.4f} within 1 nat of ln(V)={ln_v:.4f}")
    devs = leaf_devices(trainer.params)
    check(devs == {jax.devices()[0]}, f"params on the first device: {devs}")
    print(f"train: ok, losses {[round(x, 4) for x in losses]}", flush=True)
    info(f"train step 1 (compile + run) {step_s[0]:.2f} s; later steps "
         f"{', '.join(f'{s:.3f}' for s in step_s[1:])} s")


def serve_phase(extra=()):
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve
    from repro.models.model import forward_hidden, lm_logits
    from repro.serve.engine import make_decode_fn, make_prefill_fn

    t0 = time.monotonic()
    eng = serve.main(["--arch", ARCH, *SERVE_ARGS, *extra])
    info(f"serve: {len(eng.done)} requests in {time.monotonic() - t0:.2f} s "
         f"(compiles included)")
    cfg, params = eng.cfg, eng.params
    done = [eng.done[rid] for rid in sorted(eng.done)]
    n = SERVE_MAX_NEW
    check(len(done) == SERVE_REQUESTS, f"served {len(done)}/{SERVE_REQUESTS}")
    for r in done:
        check(len(r.tokens) == n
              and all(0 <= t < cfg.vocab_size for t in r.tokens),
              f"request {r.rid} tokens {r.tokens}")

    # one full forward over every served sequence, zero-padded at the end
    # (attention is causal, so padding changes no earlier position); row j
    # of ``full[i]`` is what predicts request i's j-th served token
    seqs = [np.concatenate([r.prompt, r.tokens[:-1]]) for r in done]
    toks = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, seq in enumerate(seqs):
        toks[i, :len(seq)] = seq
    full_fn = jax.jit(lambda p, t: lm_logits(p, cfg,
                                             forward_hidden(p, cfg, t)[0]))
    full_all = np.asarray(full_fn(params, jnp.asarray(toks)), np.float32)
    full = [full_all[i, len(r.prompt) - 1:len(r.prompt) - 1 + n]
            for i, r in enumerate(done)]

    # what the engine served (slot insertion, per-slot cache_len, the
    # batched decode): every token is the full forward's greedy pick, up to
    # a bf16 tie margin
    worst_gap = 0.0
    for r, f in zip(done, full):
        gap = (f.max(-1) - f[np.arange(n), r.tokens]) / np.abs(f).max()
        check(float(gap.max()) <= SERVE_ARGMAX_TOL,
              f"request {r.rid}: served {r.tokens}, full-forward argmax "
              f"{f.argmax(-1).tolist()}, gaps {np.round(gap, 4).tolist()} "
              f"x max |logit| > {SERVE_ARGMAX_TOL}")
        worst_gap = max(worst_gap, float(gap.max()))

    # the logits of prefill then decode, at batch 1, on request 0
    req, ref = done[0], full[0]
    L = len(req.prompt)
    one = jnp.asarray(toks[:1, :L + n - 1])
    prefill_fn, decode_fn = make_prefill_fn(cfg), make_decode_fn(cfg)
    logits, cache = prefill_fn(params, one[:, :L])
    rows = [logits[0]]
    for i in range(n - 1):
        logits, cache = decode_fn(params, one[:, L + i], cache,
                                  jnp.int32(L + i))
        rows.append(logits[0])
    inc = np.asarray(jnp.stack(rows), np.float32)
    err, scale = float(np.abs(inc - ref).max()), float(np.abs(ref).max())
    check(inc.shape == ref.shape == (n, cfg.vocab_size),
          f"logit shapes {inc.shape} vs {ref.shape}")
    check(bool(np.isfinite(inc).all()), "decode logits finite")
    check(err <= SERVE_LOGIT_TOL * scale,
          f"prefill+decode vs full forward: max |diff| {err:.4g} > "
          f"{SERVE_LOGIT_TOL} x max |logit| {scale:.4g}")
    print(f"serve: ok, prompt lengths {[len(r.prompt) for r in done]}; "
          f"served tokens vs full-forward argmax worst gap {worst_gap:.4g} "
          f"x max |logit|; prefill+decode vs forward max |diff| {err:.4g} "
          f"(max |logit| {scale:.4g}, prompt {L}, {n} positions)",
          flush=True)


def four_chip_phase(cfg=None):
    import gc

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import make_batch_fn
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import Trainer, TrainerConfig

    check(len(jax.devices()) == 4, f"four devices: {jax.devices()}")
    cfg = cfg or get_config(ARCH)
    shape = ShapeConfig("chip-smoke-4", FOUR_CHIP_SEQ, FOUR_CHIP_BATCH,
                        "train")
    batch_fn = make_batch_fn(cfg, shape)

    def run(mesh):
        with tempfile.TemporaryDirectory() as ckpt:
            # warmup=1: step 0 has lr 0, so step 2's loss sees one update
            tc = TrainerConfig(ckpt_dir=ckpt, ckpt_every=10**9, warmup=1,
                               total_steps=10, log_every=10**9)
            tr = Trainer(cfg, mesh, batch_fn, tc, log=lambda s: None)
            return tr, tr.run(FOUR_CHIP_STEPS)

    # the one-device reference goes first and is freed before the mesh run,
    # which shares its device
    ref = run(make_host_mesh(1, 1))[1]
    gc.collect()
    tr, out = run(make_host_mesh(2, 2))
    one, four = np.asarray(ref["losses"]), np.asarray(out["losses"])
    check(bool(np.isfinite(four).all()), f"losses finite: {four}")
    check(bool(np.allclose(four, one, rtol=0, atol=FOUR_CHIP_LOSS_TOL)),
          f"(2,2)-mesh losses {four} vs one device {one}")
    devs = leaf_devices(tr.params)
    split = [leaf for leaf in jax.tree_util.tree_leaves(tr.params)
             if leaf.addressable_shards[0].data.shape != leaf.shape]
    check(len(devs) == 4 and bool(split),
          f"params spread over 4 devices: {len(devs)} devices, "
          f"{len(split)} split leaves")
    print(f"four-chip: ok, losses {four.tolist()} vs one device "
          f"{one.tolist()}; {len(split)} param leaves split over "
          f"{len(devs)} devices", flush=True)
    info(f"(2,2) mesh step 1 (compile + run) {out['step_s'][0]:.2f} s, later "
         f"{', '.join(f'{s:.3f}' for s in out['step_s'][1:])} s; one device "
         f"step 1 {ref['step_s'][0]:.2f} s, later "
         f"{', '.join(f'{s:.3f}' for s in ref['step_s'][1:])} s")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX found {devices[0].platform} devices")
    # the train CLI builds its mesh over every visible device, and the
    # result's count is what JAX reports, so the two must agree
    check(len(devices) == args.chips,
          f"--chips {args.chips} needs exactly {args.chips} visible "
          f"devices; JAX found {len(devices)}")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    info(f"compile cache {enable_compile_cache()}")

    if args.chips == 4:
        four_chip_phase()
    else:
        train_phase()
        serve_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
