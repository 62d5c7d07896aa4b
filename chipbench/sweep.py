#!/usr/bin/env python3
"""Finds the rate an open-loop serve cell sustains (run once, by hand).

    python3 chipbench/sweep.py --workload qwen3-0.6b.chat --seed 1 \
        --rates 4 6 8 10 12 --seconds 20

One set-up, then one window per rate with the cell's traffic at that rate.
Per rate it prints the TTFT and inter-token percentiles, the requests due
and the queue left when the window closed: past the knee the queue grows
all through the window. The cell's ``rate_per_s`` is set to about four
fifths of the highest rate that keeps the queue from growing.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import spec  # noqa: E402
from chipbench.readers import p95  # noqa: E402
from chipbench.record import Run  # noqa: E402
from chipbench.run import CompileCounter, Tracer  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import jax
    from chipbench import drive_serve
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload)
    eng, _ = drive_serve.setup(Run(cell, args.seed, cell.chips, None))
    for rate in args.rates:
        cell.traffic = {**cell.traffic, "rate_per_s": rate}
        run = Run(cell, args.seed, cell.chips, None)
        compiles = CompileCounter()
        t = time.monotonic()
        tracks, failed = drive_serve.window(run, eng, args.seconds,
                                            Tracer(run, False), compiles, t)
        compiles.close()
        eng.run()
        ttft, itl = run.samples["ttft_s"], run.samples["itl_s"]
        ticks = [e - s for n, s, e in run.spans if n == "tick"]
        print(json.dumps({
            "rate_per_s": rate, "due": len(tracks), "failed": failed,
            "backlog_at_close": run.counters["backlog_at_close"],
            "ttft_p50_ms": 1e3 * statistics.median(ttft),
            "ttft_p95_ms": 1e3 * p95(ttft),
            "itl_p50_ms": 1e3 * statistics.median(itl),
            "itl_p95_ms": 1e3 * p95(itl),
            "tick_ms": 1e3 * statistics.mean(ticks),
            "out_tokens_per_s": run.counters["window_out_tokens"]
            / (run.window[1] - run.window[0]),
            "window_compiles": compiles.n}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
