#!/usr/bin/env python3
"""The program's own spans and device programs in a profiler trace.

The serve engine and the trainer write ``jax.profiler.TraceAnnotation``
spans named ``repro.*``, with their counters as arguments (README,
"Tracing"); the device's ``XLA Modules`` line names each program run by its
jitted function (``jit_serve_decode(<id>)``). ``load`` reads both beside
what ``trace_reduce.load`` reads; ``reduce`` adds, inside the traced window:

- ``program_spans``: [name without ``repro.``, start ns, end ns, {argument:
  value}] of every program span that lies inside the window;
- ``modules``: program -> [runs, device seconds], each clipped to the
  window and the mean over the devices: a run cut by an edge of the window
  counts by the share of it inside, so seconds / runs is the mean run;
- ``program_gaps``: the device's idle time split by the innermost span of
  either kind over it, program (``serve.decode``) or harness (``tick``),
  so that a harness name marks idle time that no program span covers. Its
  parts add up to the same idle total as ``idle_gaps``.

``numbers`` turns these into the per-layer quantities of PERF.md section 3.
``run.py``'s traced runs reduce their trace with ``reduce`` (``run.trace``),
so a metric reader can read any of them; ``trace_reduce.reduce``'s keys are
there as before, and the breakdown keeps to them.

Run as a script, it reduces a recorded trace:

    python3 chipbench/program_trace.py --file <trace.xplane.pb>

or runs one cell as ``run.py`` does, traced, and keeps the trace:

    python3 chipbench/program_trace.py --workload <cell> --seed <n> \\
        [--seconds <s>] [--delay <s>] [--trace-seconds <s>] [--keep <dir>]

``--delay`` starts the profiler that long into the window, and
``--trace-seconds`` sets how long it records (run.py's 5 s by default).
The last line of stdout is one JSON object: run.py's result line under
``result`` (cell runs only), the reduction's new keys, the idle totals of
both splits, and ``numbers``.
"""
from __future__ import annotations

import argparse
import json
import re
import shutil
import sys
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    REPO = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import trace_reduce  # noqa: E402

PREFIX = "repro."
MODULE = re.compile(r"^jit_(.*?)(\(\d+\))?$")
# the engine's spans that nest inside serve.admit
ADMIT = ("serve.admit", "serve.prefill", "serve.insert")


@dataclass
class ProgramTrace:
    base: trace_reduce.Trace
    # [(span name without the prefix, start ns, end ns, {arg: value})]
    spans: List[Tuple[str, float, float, Dict[str, float]]] = field(
        default_factory=list)
    # device id -> [(program, start ns, end ns)]
    modules: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)


def module_name(text: str) -> str:
    """``jit_serve_decode(123)`` -> ``serve_decode``."""
    m = MODULE.match(text)
    return m.group(1) if m else text


def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    pt = ProgramTrace(trace_reduce.from_profile(pd))
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    pt.modules[plane.name] = [
                        (module_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        pt.spans.append((e.name[len(PREFIX):], e.start_ns,
                                         e.start_ns + e.duration_ns,
                                         dict(e.stats)))
    return pt


def innermost_segments(spans, lo: float, hi: float):
    """[lo, hi] cut where any span starts or ends, each piece named by the
    shortest span that covers it (``trace_reduce.NO_SPAN`` where none
    does). Unlike ``trace_reduce.named_segments`` this looks at every span
    open at a point, however deep they nest: an engine tick holds several
    admissions of three spans each."""
    order = sorted(spans, key=lambda sp: sp[1])
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    out: List[Tuple[str, float, float]] = []
    active: list = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(order) and order[i][1] <= a:
            active.append(order[i])
            i += 1
        active = [sp for sp in active if sp[2] > a]
        name = min(active, key=lambda sp: sp[2] - sp[1])[0] if active \
            else trace_reduce.NO_SPAN
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1] = (name, out[-1][1], b)
        else:
            out.append((name, a, b))
    return out


def window(tr: trace_reduce.Trace) -> Tuple[float, float]:
    """The window ``trace_reduce.reduce`` takes by default."""
    return trace_reduce.window_of(tr) or (
        min(o[1] for ops in tr.ops.values() for o in ops),
        max(o[2] for ops in tr.ops.values() for o in ops))


def reduce(pt: ProgramTrace, win: Optional[Tuple[float, float]] = None
           ) -> Optional[dict]:
    """``trace_reduce.reduce`` of the trace, with ``program_spans``,
    ``modules`` and ``program_gaps`` added. None where the trace holds no
    device operation."""
    tr = pt.base
    out = trace_reduce.reduce(tr, win)
    if out is None:
        return None
    lo, hi = win or window(tr)
    n, ns = len(tr.ops), 1e-9
    program = [(name, s, e) for name, s, e, _ in pt.spans]
    harness = [sp for sp in tr.spans if sp[0] != "window"]
    segments = innermost_segments(program + harness, lo, hi)
    gaps: Dict[str, float] = defaultdict(float)
    for ops in tr.ops.values():
        busy = trace_reduce.union(
            [(max(s, lo), min(e, hi)) for _, s, e in trace_reduce.leaves(ops)
             if e > lo and s < hi])
        idle = trace_reduce.subtract([(lo, hi)], busy)
        for name, s, e in trace_reduce.intersect(segments, idle):
            gaps[name] += (e - s) / n
    runs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0])
    for mods in pt.modules.values():
        for name, s, e in mods:
            if e > lo and s < hi:
                inside = min(e, hi) - max(s, lo)
                runs[name][0] += (inside / (e - s) if e > s else 1) / n
                runs[name][1] += inside * ns / n
    out["program_spans"] = [[name, s, e, args]
                            for name, s, e, args in sorted(
                                pt.spans, key=lambda sp: sp[1])
                            if lo <= s and e <= hi]
    out["modules"] = dict(sorted(runs.items(), key=lambda kv: -kv[1][1]))
    out["program_gaps"] = [[k, v * ns] for k, v in
                           sorted(gaps.items(), key=lambda kv: -kv[1])]
    return out


def _mean(values) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) if values else None


def _durations_ms(red, name):
    return (1e-6 * (e - s) for n, s, e, _ in red["program_spans"]
            if n == name)


def numbers(red: Optional[dict]) -> Dict[str, float]:
    """The per-layer quantities of PERF.md section 3 that ``red`` (a
    ``reduce`` result) holds: a quantity whose spans or programs are not in
    the trace is left out."""
    if not red:
        return {}
    spans = red["program_spans"]
    args = lambda name: [a for n, _, _, a in spans if n == name]
    gaps = dict(red["program_gaps"])
    window_s = red["window_s"]
    decode = red["modules"].get("serve_decode")
    out = {
        "chat.queue_wait_ms": _mean(1e-3 * a["wait_us"]
                                    for a in args("serve.admit")),
        "chat.prefill_ms": _mean(_durations_ms(red, "serve.prefill")),
        "chat.decode_device_ms": 1e3 * decode[1] / decode[0]
        if decode and decode[0] else None,
        "chat.slot_occupancy": _mean(100.0 * a["active"] / a["slots"]
                                     for a in args("serve.decode")),
        "chat.admit_idle": 100.0 * sum(gaps.get(k, 0.0) for k in ADMIT)
        / window_s if args("serve.admit") else None,
        "chat.sample_idle": 100.0 * gaps.get("serve.sample", 0.0) / window_s
        if args("serve.sample") else None,
        "train.dispatch_ms": _mean(_durations_ms(red, "train.dispatch")),
    }
    return {k: v for k, v in out.items() if v is not None}


def summary(red: Optional[dict]) -> dict:
    """What the script prints of a reduction."""
    if not red:
        return {}
    return {"window_s": red["window_s"], "busy_s": red["busy_s"],
            "idle_s": red["window_s"] - red["busy_s"],
            "idle_gaps_s": sum(v for _, v in red["idle_gaps"]),
            "program_gaps_s": sum(v for _, v in red["program_gaps"]),
            "idle_gaps": red["idle_gaps"],
            "program_gaps": red["program_gaps"],
            "modules": red["modules"],
            "program_spans": len(red["program_spans"]),
            "numbers": numbers(red)}


def kept_tracer(store: dict, keep: Optional[str], delay_s: float,
                limit_s: float):
    """run.py's Tracer, except that it starts ``delay_s`` into the window,
    records ``limit_s``, copies the trace to ``keep``, and keeps its
    reduction in ``store["trace"]`` (run.py deletes its trace once
    reduced)."""
    from chipbench import run as bench_run

    class KeptTracer(bench_run.Tracer):
        def __init__(self, run, on):
            super().__init__(run, on, limit_s)
            self.begin = None

        def start(self):
            self.begin = time.monotonic()
            if delay_s <= 0:
                super().start()

        def after(self):
            if (self.on and self.dir is None and self.begin is not None
                    and time.monotonic() - self.begin >= delay_s):
                super().start()
            super().after()

        def reduce_file(self, path):
            if keep:
                Path(keep).mkdir(parents=True, exist_ok=True)
                shutil.copy(path, Path(keep) / Path(path).name)
            store["trace"] = super().reduce_file(path)
            return store["trace"]

    return KeptTracer


def run_cell(cell, seed: int, seconds: float, *, devices, peaks,
             t_start: float, keep: Optional[str] = None,
             delay_s: float = 0.0, limit_s: float = 5.0,
             exact: bool = True):
    """One traced run of ``cell`` through ``run.run_cell``, with run.py's
    Tracer swapped for ``kept_tracer``: (its result line, the reduction
    with the program's keys, or None)."""
    from chipbench import run as bench_run
    store: dict = {}
    plain = bench_run.Tracer
    bench_run.Tracer = kept_tracer(store, keep, delay_s, limit_s)
    try:
        out = bench_run.run_cell(cell, seed, seconds, True, devices=devices,
                                 peaks=peaks, t_start=t_start, exact=exact)
    finally:
        bench_run.Tracer = plain
    return out, store.get("trace")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--file")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float,
                    help="window length (default: BENCHMARK.json's "
                    "run_seconds; a serve cell's schedule depends on it)")
    ap.add_argument("--delay", type=float, default=0.0)
    ap.add_argument("--trace-seconds", type=float, default=5.0)
    ap.add_argument("--keep")
    args = ap.parse_args(argv)
    if bool(args.file) == bool(args.workload):
        ap.error("give one of --file and --workload")
    if args.file:
        print(json.dumps(summary(reduce(load(args.file)))), flush=True)
        return 0

    from chipbench import run as bench_run, spec, flops
    cell = spec.load_cell(args.workload)
    seconds = args.seconds or spec.load_benchmark()["run_seconds"]
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"program_trace: {args.workload} needs {cell.chips} TPU "
              f"chip(s)", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    out, red = run_cell(cell, args.seed, seconds, devices=devices,
                        peaks=flops.peaks(devices[0].device_kind),
                        t_start=bench_run.T_START, keep=args.keep,
                        delay_s=args.delay, limit_s=args.trace_seconds)
    print(json.dumps({"result": out, **summary(red)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
