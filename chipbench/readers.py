"""Arithmetic shared by the metric readers in metrics/."""
from __future__ import annotations

import math
from typing import List, Optional


def p95(values: List[float]) -> Optional[float]:
    """Nearest-rank 95th percentile."""
    if not values:
        return None
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)]


def mean_span_ms(run, name: str) -> Optional[float]:
    spans = run.spans_in(name, *run.measured)
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)


def idle_pct(run) -> Optional[float]:
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def flops_pct(run, seconds: Optional[float]) -> Optional[float]:
    """Model FLOPs done in the measured interval over seconds x chips x
    the chip's bf16 peak, in %."""
    flops = run.flops_in(*run.measured)
    if not flops or not seconds or not run.peaks:
        return None
    return 100.0 * flops / (seconds * run.chips * run.peaks["bf16_flops"])


def measured_s(run) -> float:
    lo, hi = run.measured
    return hi - lo
