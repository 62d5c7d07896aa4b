#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

    python3 chipbench/control.py --workload <cell> --seeds 1 2 ... \
        --control-seeds 3 --seconds 10

In one process, for each seed: the cell's set-up and a short window at its
own load, then the check's numbers for the program against the float32
reference (the lower readings). For the first ``--control-seeds`` seeds
also the numbers of the control, the reference computed with fp8 matrix
products put in the program's place, and for training cells those of a
planted fault, half the batch left out with the mean taken over the rest,
and of another, every step at 0.7 of the learning rate (the upper
readings). One JSON line per seed; the last line sums up.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import spec  # noqa: E402
from chipbench.record import Run  # noqa: E402
from chipbench.run import CompileCounter, Tracer, driver  # noqa: E402


# the planted fault ``lr_low``: every step taken at this share of the
# learning rate
LR_FAULT = 0.7


def half_batch(batches):
    """Fault: each batch's first half of rows stands for the whole."""
    return [np.concatenate([b[: len(b) // 2]] * 2) for b in batches]


def readings_for(cell, seed, seconds, control, peaks, exact=True):
    d = driver(cell.traffic["kind"])
    run = Run(cell, seed, cell.chips, peaks)
    compiles = CompileCounter()
    try:
        readings, attempted, failed, memory = d.drive(
            run, seconds, Tracer(run, False), compiles, time.monotonic(),
            exact)
    finally:
        compiles.close()
    out = d.numbers(run, readings, control=control)
    if control and cell.traffic["kind"] == "train":
        from chipbench import check
        full = d.reference_readings(run)
        faults = {"half_batch": d.reference_readings(run, transform=half_batch),
                  "lr_low": d.reference_readings(run, lr_scale=LR_FAULT)}
        for name, f in faults.items():
            out.update({f"{name}_{k}": v for k, v in
                        check.train_numbers(f, full).items()})
    out.update(seed=seed, attempted=attempted, failed=failed,
               memory_peak_bytes=memory)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    import jax
    from chipbench import flops
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    peaks = flops.peaks(devices[0].device_kind)
    rows = []
    for i, seed in enumerate(args.seeds):
        t = time.monotonic()
        row = readings_for(cell, seed, args.seconds,
                           i < args.control_seeds, peaks)
        row["seconds"] = time.monotonic() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps({"workload": args.workload, **row}) + "\n")
    summary = {"workload": args.workload, "upper": {},
               "lower": {k: max(r[k] for r in rows) for k in cell.limits}}
    for k in cell.limits:
        for p in ("control_", "half_batch_", "lr_low_"):
            vals = [r[p + k] for r in rows if p + k in r]
            if vals:
                summary["upper"][p + k] = min(vals)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
