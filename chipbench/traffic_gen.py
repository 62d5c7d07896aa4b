"""The one generator that every traffic file is read by.

Training traffic is rows of tokens. Serving traffic is requests: a prompt,
a number of tokens to generate, and (open loop) the time each is due.

A cell's sizes and arrival gaps are drawn once from the traffic file's
``catalog_seed``; ``--seed`` fills the prompts with tokens. So every seed
offers the same work.
Prompt lengths come from a catalog of ``prompt.lengths`` lengths, because
the serve engine compiles one prefill program per prompt length: set-up
warms each of them once.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np


def train_batch(seed: int, step: int, batch: int, seq_len: int,
                vocab: int) -> np.ndarray:
    """Rows of step ``step``: the arithmetic of the program's synthetic
    stream (repro.data.pipeline.SyntheticLM), copied so that the reference
    is fed without the program's help."""
    rng = np.random.Generator(np.random.Philox(key=seed, counter=step))
    return rng.integers(0, vocab, size=(batch, seq_len), dtype=np.int32)


def _lognormal_ints(rng, n: int, spec: Dict[str, Any]) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def prompt_catalog(traffic: Dict[str, Any]) -> np.ndarray:
    """The prompt lengths this cell uses, sorted, from its catalog seed."""
    rng = np.random.default_rng([traffic["catalog_seed"], 0])
    return np.sort(_lognormal_ints(rng, traffic["prompt"]["lengths"],
                                   traffic["prompt"]))


@dataclass
class Req:
    index: int
    due_s: float          # offset from the window's start (open loop)
    prompt: np.ndarray    # int32 token ids
    max_new: int


def _sizes(traffic: Dict[str, Any], n: int):
    """n (prompt length, output length) pairs from the catalog seed."""
    rng = np.random.default_rng([traffic["catalog_seed"], 1, n])
    cat = prompt_catalog(traffic)
    lens = cat[rng.integers(0, len(cat), size=n)]
    outs = _lognormal_ints(rng, n, traffic["output"])
    return lens, outs


def _prompt(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    rng = np.random.Generator(np.random.Philox(key=seed + (1 << 64),
                                               counter=index))
    return rng.integers(0, vocab, size=length, dtype=np.int32)


def poisson_requests(traffic: Dict[str, Any], seed: int, seconds: float,
                     vocab: int) -> List[Req]:
    """Open loop: round(rate x seconds) requests at the cell's mean rate,
    the first due at the window's start. Gaps (exponential, scaled so that
    their mean is exactly 1/rate) and sizes come from the catalog seed
    alone, in one fixed order: a tail such as a 95th percentile depends on
    the order of arrivals, so every seed offers the same schedule and
    fills its prompts with other tokens."""
    rate = traffic["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    lens, outs = _sizes(traffic, n)
    gaps = np.random.default_rng([traffic["catalog_seed"], 2, n]) \
        .exponential(1.0 / rate, size=n)
    gaps *= (n / rate) / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])   # first at 0
    return [Req(i, float(due[i]), _prompt(seed, i, int(lens[i]), vocab),
                int(outs[i])) for i in range(n)]


# arrival kind (a traffic file's ``arrival``) -> its request schedule
ARRIVALS = {"poisson": poisson_requests}


def requests(traffic: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Req]:
    """The requests of one window, by the traffic file's arrival kind."""
    kind = traffic["arrival"]
    if kind not in ARRIVALS:
        raise ValueError(f"unknown arrival kind {kind!r}; "
                         f"have {sorted(ARRIVALS)}")
    return ARRIVALS[kind](traffic, seed, seconds, vocab)
