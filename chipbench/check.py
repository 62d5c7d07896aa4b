"""The numbers that decide ``correct``, and the judgement against limits.

Training: each checked step's loss, the norm of the first step's gradient
as the optimizer gets it, and the norm of the parameters' change over the
checked steps. The norms are compared leaf by leaf (a stacked per-layer
leaf counts one leaf per layer): the gap between the program's norm and
the reference's, over the larger of the reference's norm of that leaf and
the median leaf's, and the worst leaf is the number. Leaves whose
reference gradient is under a thousandth of the median leaf's are left out
of the change, since Adam moves them by round-off alone.

Serving: the widest gap by which a served token's reference logit lies
below the reference's best logit at that position, over the standard
deviation of the reference's logits there.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, Optional, Tuple

GRAD_FLOOR = 1e-3


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
               keys: Optional[Iterable[str]] = None) -> Tuple[float, str]:
    keys = sorted(ref if keys is None else keys)
    med = statistics.median(ref[k] for k in keys)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = math.inf
    grad_gap, grad_leaf = worst_leaf(prog["grad_norms"], ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    moved = [k for k, g in ref["grad_norms"].items()
             if g >= GRAD_FLOOR * med]
    change_gap, change_leaf = worst_leaf(prog["change_norms"],
                                         ref["change_norms"], moved)
    dropped = sorted(set(ref["grad_norms"]) - set(moved))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap,
            "_where": f"grad {grad_leaf}, change {change_leaf}",
            "_dropped": ",".join(dropped) or "none"}


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}) for every limited number."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name, math.nan)
        checks[name] = {"value": v, "limit": limit}
        ok = ok and math.isfinite(v) and v <= limit
    return ok, checks
