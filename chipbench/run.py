#!/usr/bin/env python3
"""Runs one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (weights from the seed, every shape the cell's traffic uses warmed,
programs from JAX's persistent cache in <checkout>/.jax_cache) counts as
``setup_s``; then the window measures for --seconds. With --trace 1 the
profiler records the first TRACE_S seconds of the window, and the line
carries the per-layer metrics instead of the end-to-end ones, read from
``program_trace.reduce`` of the trace (``run.trace``). After the
window the program's state is freed and the plain float32 reference
decides ``correct``.

The last line of stdout is one JSON object: correct, attempted, failed,
metrics, device, (traced) breakdown, and last ``checks``, each number
compared beside its limit; the checks are also the last lines of stderr.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import spec  # noqa: E402
from chipbench.record import Run  # noqa: E402

TRACE_S = 5.0
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Tracer:
    """Starts the profiler at the window's start and stops it once
    ``limit_s`` have passed (or at the window's end)."""

    def __init__(self, run: Run, on: bool, limit_s: float = TRACE_S):
        self.run, self.on, self.limit_s = run, on, limit_s
        self.dir = self.ann = None
        self.t0 = 0.0

    def start(self):
        if not self.on:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self.dir, profiler_options=_options())
        self.ann = jax.profiler.TraceAnnotation("bench.window")
        self.ann.__enter__()
        self.t0 = time.monotonic()

    def after(self):
        if self.ann is not None and time.monotonic() - self.t0 >= self.limit_s:
            self.stop()

    def stop(self):
        if self.ann is None:
            return
        import jax
        self.ann.__exit__(None, None, None)
        self.ann = None
        self.run.traced = (self.t0, time.monotonic())
        jax.profiler.stop_trace()

    def reduce(self):
        """The trace's reduction (None where there is none), then the trace
        is deleted."""
        if self.dir is None:
            return None
        try:
            path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            return self.reduce_file(path[0]) if path else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

    def reduce_file(self, path: str):
        """``program_trace.reduce``: trace_reduce's busy time, top ops and
        idle gaps, with the program's spans and device programs."""
        from chipbench import program_trace
        return program_trace.reduce(program_trace.load(path))


def _options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


class CompileCounter:
    """Counts programs lowered while ``active`` (a jax.monitoring event)."""

    def __init__(self):
        import jax
        self.active, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.active and event == COMPILE_EVENT:
            self.n += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def driver(kind: str):
    """The module chipbench/drive_<kind>.py that drives traffic of
    ``kind`` (a traffic file's ``kind``)."""
    if not (spec.HERE / f"drive_{kind}.py").is_file():
        have = sorted(p.stem[len("drive_"):]
                      for p in spec.HERE.glob("drive_*.py"))
        raise ValueError(f"unknown traffic kind {kind!r}; have {have}")
    return importlib.import_module(f"chipbench.drive_{kind}")


def run_cell(cell, seed: int, seconds: float, trace: bool, *, devices,
             peaks, t_start: float, exact: bool = True):
    """One run of ``cell``: returns the result line's object."""
    from chipbench import check
    run = Run(cell, seed, cell.chips, peaks)
    d = driver(cell.traffic["kind"])
    tracer, compiles = Tracer(run, trace), CompileCounter()
    try:
        readings, attempted, failed, memory = d.drive(
            run, seconds, tracer, compiles, t_start, exact)
    finally:
        tracer.stop()
        compiles.close()
    numbers = d.numbers(run, readings)
    correct, checks = check.judge(numbers, cell.limits)
    correct = correct and failed == 0
    run.trace = tracer.reduce()
    group = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in group:
        v = spec.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    extra = {k: v for k, v in numbers.items() if k not in checks}
    print(f"counters: {run.counters}", file=sys.stderr)
    print(f"not limited: {extra}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    import jax
    from chipbench import flops
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # cache every program, however quick to compile, so that a second run
    # of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}", file=sys.stderr)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices=devices, peaks=flops.peaks(devices[0].device_kind),
                   t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
