"""Data pipeline: deterministic synthetic stream + packed binary corpus.

Both sources are *stateless by step index* — batch(step) is a pure function
of (seed, step) — which makes checkpoint/restart trivial (no iterator state
to persist) and keeps every data-parallel host reproducible after elastic
rescale: host h of H loads rows [h::H] of the global batch.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np


@dataclass
class SyntheticLM:
    """Deterministic synthetic token stream (markov-ish, cheap to generate)."""
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    num_hosts: int = 1
    host_id: int = 0

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.Generator(np.random.Philox(key=self.seed, counter=step))
        B, T = self.global_batch, self.seq_len
        # draw the GLOBAL batch, then slice this host's rows — every host
        # must see a distinct partition of the same global batch
        tokens = rng.integers(0, self.vocab_size, size=(B, T),
                              dtype=np.int32)
        lo = B * self.host_id // self.num_hosts
        hi = B * (self.host_id + 1) // self.num_hosts
        tokens = tokens[lo:hi]
        return {"tokens": tokens, "labels": tokens.copy()}


class PackedBinReader:
    """Memmap'd packed-token corpus (.bin of uint16/uint32).

    Sampling is deterministic in (seed, step): window offsets are drawn from
    a counter-based RNG, so restart/rescale re-reads identical data.
    """

    def __init__(self, path: str, seq_len: int, global_batch: int,
                 dtype=np.uint16, seed: int = 0, num_hosts: int = 1,
                 host_id: int = 0):
        self.data = np.memmap(path, dtype=dtype, mode="r")
        self.seq_len = seq_len
        self.global_batch = global_batch
        self.seed = seed
        self.num_hosts = num_hosts
        self.host_id = host_id
        self.n_tokens = len(self.data)
        if self.n_tokens < seq_len + 1:
            raise ValueError(f"corpus too small: {self.n_tokens} tokens")

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.Generator(np.random.Philox(key=self.seed,
                                                   counter=step))
        B, T = self.global_batch, self.seq_len
        offs = rng.integers(0, self.n_tokens - T - 1, size=B)
        lo = B * self.host_id // self.num_hosts
        hi = B * (self.host_id + 1) // self.num_hosts
        rows = [np.asarray(self.data[o:o + T], dtype=np.int32)
                for o in offs[lo:hi]]
        arr = np.stack(rows)
        # contract: labels == tokens; forward_loss applies the next-token
        # shift internally (targets = labels[:, 1:] vs logits[:, :-1]).
        return {"tokens": arr, "labels": arr.copy()}

    @staticmethod
    def write_corpus(path: str, tokens: np.ndarray, dtype=np.uint16):
        np.asarray(tokens, dtype=dtype).tofile(path)


def make_batch_fn(cfg, shape, seed: int = 0, corpus: Optional[str] = None):
    """Returns batch(step) for (arch cfg, ShapeConfig): the packed corpus
    at ``corpus`` when one is named (a missing file raises), else the
    synthetic stream."""
    if corpus:
        src = PackedBinReader(corpus, shape.seq_len, shape.global_batch,
                              seed=seed)
    else:
        src = SyntheticLM(cfg.vocab_size, shape.seq_len, shape.global_batch,
                          seed=seed)
    return src.batch
