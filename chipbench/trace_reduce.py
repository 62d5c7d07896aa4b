"""From a profiler trace to device busy time, top ops, idle gaps and
exposed collective time.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain tuples: each device's operations (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) and the harness's host spans (TraceAnnotation
events whose names start with ``bench.``). ``reduce`` is arithmetic on
those tuples alone, so it can be checked without a chip.

- leaf ops: a device's operations less those whose interval holds another
  of its operations (a ``while`` or ``conditional`` and the ops of its
  body share the line), so that no time counts twice and the ranking
  names the ops that do the work.
- busy: the union of a device's leaf op intervals inside the window;
  ``busy_s`` is its mean over the devices.
- idle gaps: the window less the busy union, each gap split over the
  harness spans it overlaps on the host's clock, each part named by the
  innermost span there.
- collectives: all-reduce, all-gather, reduce-scatter, all-to-all and
  collective-permute ops; exposed is the part of their union during which
  no other operation runs on that device.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|allreduce|allgather|reducescatter", re.I)
SPAN_PREFIX = "bench."
NO_SPAN = "(no harness span)"


@dataclass
class Trace:
    # device id -> [(op name, start ns, end ns)]
    ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    # [(span name without the prefix, start ns, end ns)]
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the TPU
    trace names each op by its whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(path)))


def from_profile(pd) -> Trace:
    """The ops and harness spans of a ``jax.profiler.ProfileData``."""
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops[plane.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.name[len(SPAN_PREFIX):],
                                         e.start_ns,
                                         e.start_ns + e.duration_ns))
    return tr


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of union ``a`` not covered by union ``b`` (both sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(ops: List[Tuple[str, float, float]]):
    """The ops of one line whose interval holds no other op of the line."""
    order = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, (n, s, e) in enumerate(order):
        j = i + 1
        while j < len(order) and order[j][1] < e:
            if order[j][2] <= e and order[j][1:] != (s, e):
                break           # op j lies inside op i
            j += 1
        else:
            out.append((n, s, e))
    return out


def intersect(segments, gaps: List[Interval]):
    """The parts of ``gaps`` (sorted, disjoint) inside each named segment
    (sorted, disjoint): [(name, start, end)]."""
    out, j = [], 0
    for name, s, e in segments:
        while j < len(gaps) and gaps[j][1] <= s:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < e:
            a, b = max(s, gaps[k][0]), min(e, gaps[k][1])
            if b > a:
                out.append((name, a, b))
            k += 1
    return out


def _span_at(spans, starts, t: float, depth: int = 4) -> str:
    """The shortest span that covers ``t`` among the ``depth`` latest to
    start before it (harness spans nest a few deep and do not overlap
    otherwise). ``spans`` is sorted by start; ``starts`` are their starts."""
    best: Optional[Tuple[float, str]] = None
    i = bisect.bisect_right(starts, t)
    for name, s, e in spans[max(0, i - depth):i]:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else NO_SPAN


def named_segments(spans, lo: float, hi: float) -> List[Tuple[str, float,
                                                               float]]:
    """[lo, hi] cut where any span starts or ends, each piece named by the
    innermost span over it (``spans`` sorted by start)."""
    starts = [sp[1] for sp in spans]
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    out: List[Tuple[str, float, float]] = []
    for s, e in zip(cuts, cuts[1:]):
        name = _span_at(spans, starts, (s + e) / 2)
        if out and out[-1][0] == name and out[-1][2] == s:
            out[-1] = (name, out[-1][1], e)
        else:
            out.append((name, s, e))
    return out


def window_of(tr: Trace, name: str = "window") -> Optional[Interval]:
    """The host span ``bench.<name>`` that the harness put round the
    traced window, if there is one."""
    for n, s, e in tr.spans:
        if n == name:
            return (s, e)
    return None


def reduce(tr: Trace, window: Optional[Interval] = None,
           top: int = 10) -> Optional[dict]:
    """Busy and idle time, top ops and exposed collectives in ``window``
    (default: the ``window`` span, else the extent of the device ops).
    Returns None where the trace holds no device operation."""
    if not any(tr.ops.values()):
        return None
    if window is None:
        window = window_of(tr)
    if window is None:
        window = (min(o[1] for ops in tr.ops.values() for o in ops),
                  max(o[2] for ops in tr.ops.values() for o in ops))
    lo, hi = window
    busy, coll, exposed = [], [], []
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    inner = sorted((sp for sp in tr.spans if sp[0] != "window"),
                   key=lambda sp: sp[1])
    segments = named_segments(inner, lo, hi)
    for dev, ops in sorted(tr.ops.items()):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in leaves(ops)
               if e > lo and s < hi]
        u = union([(s, e) for _, s, e in ops])
        busy.append(total(u))
        for n, s, e in ops:
            op_time[n] += (e - s) / len(tr.ops)
        c = union([(s, e) for n, s, e in ops if COLLECTIVE.search(n)])
        other = union([(s, e) for n, s, e in ops
                       if not COLLECTIVE.search(n)])
        coll.append(total(c))
        exposed.append(total(subtract(c, other)))
        for name, s, e in intersect(segments, subtract([(lo, hi)], u)):
            gap_time[name] += (e - s) / len(tr.ops)
    n = len(tr.ops)
    ns = 1e-9
    rank = lambda d: [[k, v * ns] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(busy) / n * ns, "window_s": (hi - lo) * ns,
            "collective_s": sum(coll) / n * ns,
            "exposed_collective_s": sum(exposed) / n * ns,
            "devices": n, "device_ops": rank(op_time),
            "idle_gaps": rank(gap_time)}
