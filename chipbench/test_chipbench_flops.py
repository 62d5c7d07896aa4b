"""FLOP and byte counts against a hand count on a small config."""
from __future__ import annotations

import pytest

from chipbench import flops, spec

# d 8, 2 heads x 4 (q 8), 1 kv head (kv 4), ffn 16, 2 layers, vocab 10
SMALL = {"bench_arch": "qwen_dense", "num_hidden_layers": 2,
         "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
         "vocab_size": 10, "tie_word_embeddings": True,
         "attention_bias": True, "qk_norm": True}
DENSE = spec.arch(SMALL)


def test_matmul_params_by_hand():
    # per layer: wq 8x8=64, wk 8x4=32, wv 32, wo 64, gate/up/down 3x128=384
    assert DENSE.layer_matmul_params(SMALL) == 576
    assert DENSE.matmul_params(SMALL) == 2 * 576 + 10 * 8


def test_train_step_by_hand():
    B, T = 3, 5
    matmul = 6 * (2 * 576 + 80) * B * T
    # causal pairs B*T*T/2; per pair 4 * layers * heads * head_dim, x3
    attn = 3 * 4 * 2 * 2 * 4 * B * T * T / 2
    assert flops.train_step(SMALL, B, T) == matmul + attn


def test_prefill_and_decode_by_hand():
    assert flops.prefill(SMALL, 6) == (2 * 2 * 576 * 6 + 2 * 80
                                       + 4 * 2 * 2 * 4 * 36 / 2)
    assert flops.decode_token(SMALL, 7) == 2 * 1232 + 4 * 2 * 2 * 4 * 7


def test_bytes_by_hand():
    assert DENSE.cache_bytes_per_token(SMALL) == 2 * 2 * 1 * 4 * 2
    # 1152 layer matmul + 80 tied embedding + 2x2x8 norms + final 8
    # + biases 2x(8+4+4) + qk norms 2x2x4
    n = 1152 + 80 + 32 + 8 + 32 + 16
    assert DENSE.param_bytes(SMALL) == 2 * n
    assert flops.decode_step_bytes(SMALL, [3, 0]) == 2 * n + 32 * (4 + 1)


def test_peaks_table():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
