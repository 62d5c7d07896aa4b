"""Data pipeline: determinism, host sharding, packed corpus."""
from __future__ import annotations

import numpy as np
import pytest

from repro.data.pipeline import PackedBinReader, SyntheticLM, make_batch_fn
from repro.configs import get_config
from repro.configs.base import SHAPES


def test_synthetic_deterministic_by_step():
    src = SyntheticLM(vocab_size=100, seq_len=16, global_batch=4, seed=7)
    b1 = src.batch(3)
    b2 = src.batch(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = src.batch(4)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_synthetic_host_sharding_partitions():
    """Union of per-host slices == the single-host global batch, disjoint."""
    full = SyntheticLM(100, 16, 8, seed=1).batch(0)["tokens"]
    parts = [SyntheticLM(100, 16, 8, seed=1, num_hosts=4, host_id=h)
             .batch(0)["tokens"] for h in range(4)]
    assert all(p.shape == (2, 16) for p in parts)
    np.testing.assert_array_equal(np.concatenate(parts), full)


def test_synthetic_tokens_in_vocab():
    b = SyntheticLM(37, 16, 4).batch(0)
    assert b["tokens"].min() >= 0 and b["tokens"].max() < 37
    assert b["tokens"].dtype == np.int32
    np.testing.assert_array_equal(b["tokens"], b["labels"])


def test_packed_corpus_roundtrip(tmp_path):
    path = str(tmp_path / "corpus.bin")
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 1000, size=10_000)
    PackedBinReader.write_corpus(path, toks)
    rd = PackedBinReader(path, seq_len=32, global_batch=4, seed=5)
    b1 = rd.batch(0)
    assert b1["tokens"].shape == (4, 32)
    b2 = rd.batch(0)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # every row is a contiguous window of the corpus
    for row in b1["tokens"]:
        starts = np.where(toks == row[0])[0]
        assert any(np.array_equal(toks[s:s + 32], row) for s in starts)


def test_packed_corpus_host_sharding(tmp_path):
    path = str(tmp_path / "c.bin")
    PackedBinReader.write_corpus(path, np.arange(5000) % 500)
    full = PackedBinReader(path, 16, 8, seed=2).batch(1)["tokens"]
    parts = [PackedBinReader(path, 16, 8, seed=2, num_hosts=2,
                             host_id=h).batch(1)["tokens"] for h in range(2)]
    np.testing.assert_array_equal(np.concatenate(parts), full)


def test_corpus_too_small_raises(tmp_path):
    path = str(tmp_path / "tiny.bin")
    PackedBinReader.write_corpus(path, np.arange(10))
    with pytest.raises(ValueError):
        PackedBinReader(path, seq_len=32, global_batch=1)


def test_make_batch_fn_shapes():
    cfg = get_config("qwen3_0_6b").reduced()
    shape = SHAPES["train_4k"]
    fn = make_batch_fn(cfg, shape)
    b = fn(0)
    assert b["tokens"].shape == (shape.global_batch, shape.seq_len)
    assert b["tokens"].max() < cfg.vocab_size


def test_make_batch_fn_missing_corpus_raises(tmp_path):
    """A named corpus that does not exist is an error, never synthetic data."""
    cfg = get_config("qwen3_0_6b").reduced()
    with pytest.raises(FileNotFoundError):
        make_batch_fn(cfg, SHAPES["train_4k"],
                      corpus=str(tmp_path / "missing.bin"))
