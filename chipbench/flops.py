"""Operations and bytes that the algorithm needs, from the sizes alone.

Model FLOPs count each multiply-add as two operations and leave out
recomputation (remat), so a utilization built on them is model FLOP
utilization. Matrix products of the decoder layers and the LM head count;
the embedding lookup, norms and elementwise work do not. Causal attention
counts the half of the score matrix that the mask keeps.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: "
                       f"{[k for k in table if not k.startswith('_')]}")
    return table[device_kind]


def layer_matmul_params(c: Dict[str, Any]) -> int:
    """Weights of one decoder layer's matrix products."""
    d, hd, F = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * F


def matmul_params(c: Dict[str, Any]) -> int:
    """Non-embedding matmul weights plus the LM head."""
    return (c["num_hidden_layers"] * layer_matmul_params(c)
            + c["vocab_size"] * c["hidden_size"])


def _attn(c: Dict[str, Any]) -> int:
    """FLOPs per (query, key) pair over all layers: QK^T and PV."""
    return (4 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"])


def train_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Forward and backward (3x forward) of one step over batch x seq."""
    tokens = batch * seq
    causal_pairs = batch * seq * seq / 2
    return 3.0 * (2.0 * matmul_params(c) * tokens + _attn(c) * causal_pairs)


def prefill(c: Dict[str, Any], length: int) -> float:
    """One prompt of ``length`` tokens; the LM head runs on its last row."""
    layers = c["num_hidden_layers"] * layer_matmul_params(c)
    return (2.0 * layers * length + 2.0 * c["vocab_size"] * c["hidden_size"]
            + _attn(c) * length * length / 2)


def decode_token(c: Dict[str, Any], context: int) -> float:
    """One generated token that attends to ``context`` positions."""
    return 2.0 * matmul_params(c) + _attn(c) * context


def kv_bytes_per_token(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * dtype_bytes)


def param_bytes(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    d, L, hd = c["hidden_size"], c["num_hidden_layers"], c["head_dim"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    n = matmul_params(c) - c["vocab_size"] * d          # layers
    n += c["vocab_size"] * d * (1 if c["tie_word_embeddings"] else 2)
    n += L * 2 * d + d                                  # norms
    if c["attention_bias"]:
        n += L * (q + 2 * kv)
    if c["qk_norm"]:
        n += L * 2 * hd
    return n * dtype_bytes


def decode_step_bytes(c: Dict[str, Any], contexts) -> int:
    """Bytes one batched decode step must read: every weight once and the
    KV cache of each sequence's context, plus the new K and V it writes."""
    kv = kv_bytes_per_token(c)
    return param_bytes(c) + sum(kv * (n + 1) for n in contexts)
