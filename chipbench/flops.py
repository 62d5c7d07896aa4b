"""Operations and bytes that the algorithm needs, from the sizes alone.

Model FLOPs count each multiply-add as two operations and leave out
recomputation (remat), so a utilization built on them is model FLOP
utilization. Matrix products of the decoder layers and the LM head count;
the embedding lookup, norms and elementwise work do not. Causal attention
counts the half of the score matrix that the mask keeps. The
configuration's arch module gives the counts these are built from: the
matmul weights a token passes through (``matmul_params``, the LM head's
included; active ones only where there are experts), the FLOPs of one
(query, key) pair (``attn_pair_flops``), the cache bytes of one token
(``cache_bytes_per_token``) and the weights' bytes (``param_bytes``).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

from chipbench import spec

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: "
                       f"{[k for k in table if not k.startswith('_')]}")
    return table[device_kind]


def train_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Forward and backward (3x forward) of one step over batch x seq."""
    arch = spec.arch(c)
    tokens = batch * seq
    causal_pairs = batch * seq * seq / 2
    return 3.0 * (2.0 * arch.matmul_params(c) * tokens
                  + arch.attn_pair_flops(c) * causal_pairs)


def prefill(c: Dict[str, Any], length: int) -> float:
    """One prompt of ``length`` tokens; the LM head runs on its last row."""
    arch = spec.arch(c)
    lm_head = c["vocab_size"] * c["hidden_size"]
    layers = arch.matmul_params(c) - lm_head
    return (2.0 * layers * length + 2.0 * lm_head
            + arch.attn_pair_flops(c) * length * length / 2)


def decode_token(c: Dict[str, Any], context: int) -> float:
    """One generated token that attends to ``context`` positions."""
    arch = spec.arch(c)
    return 2.0 * arch.matmul_params(c) + arch.attn_pair_flops(c) * context


def decode_step_bytes(c: Dict[str, Any], contexts) -> int:
    """Bytes one batched decode step must read: every weight once and the
    cache of each sequence's context, plus the new entry it writes."""
    arch = spec.arch(c)
    per_token = arch.cache_bytes_per_token(c)
    return arch.param_bytes(c) + sum(per_token * (n + 1) for n in contexts)
