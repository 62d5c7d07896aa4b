"""What one run records, for the metric readers to read.

The harness stamps spans itself, on the host's monotonic clock, around
each call it makes into the program (``step``, ``batch``, ``tick``); each
span is also a ``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so
a traced run finds it on the trace's clock beside the device's work.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax


@dataclass
class Run:
    cell: Any                               # spec.Cell
    seed: int
    chips: int
    peaks: Optional[Dict[str, float]]       # peaks.json entry of the chip
    setup_s: float = 0.0
    window: Tuple[float, float] = (0.0, 0.0)
    # the part of the window the profiler saw (traced runs only)
    traced: Optional[Tuple[float, float]] = None
    trace: Optional[dict] = None            # trace_reduce.reduce(...)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    counters: Dict[str, float] = field(default_factory=dict)
    samples: Dict[str, List[float]] = field(default_factory=dict)
    # (host time the work was seen done, model FLOPs of that work)
    work: List[Tuple[float, float]] = field(default_factory=list)

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            t0 = time.monotonic()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.monotonic()))

    def spans_in(self, name: str, lo: float, hi: float):
        return [(s, e) for n, s, e in self.spans
                if n == name and s >= lo and e <= hi]

    def flops_in(self, lo: float, hi: float) -> float:
        return sum(f for t, f in self.work if lo < t <= hi)

    @property
    def measured(self) -> Tuple[float, float]:
        """The interval per-layer metrics read: the traced part of the
        window in a traced run, else the whole window."""
        return self.traced or self.window
