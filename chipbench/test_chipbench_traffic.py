"""The generator: the same seed gives the same traffic, another seed the
same sizes in another order."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import spec, traffic_gen


def _traffic(name):
    return spec.load_json(spec.HERE / "traffic" / f"{name}.json")


def _reqs(seed):
    return traffic_gen.requests(_traffic("chat_poisson"), seed, 30.0,
                                151936)


def test_poisson_same_seed_same_traffic():
    a, b = _reqs(2**31 + 5), _reqs(2**31 + 5)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


def test_poisson_other_seed_same_schedule_other_tokens():
    a, b = _reqs(1), _reqs(2)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert a[0].due_s == 0.0
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])


def test_poisson_rate_and_catalog():
    tr = _traffic("chat_poisson")
    reqs = _reqs(3)
    assert len(reqs) == round(tr["rate_per_s"] * 30.0)
    cat = set(traffic_gen.prompt_catalog(tr).tolist())
    assert {len(r.prompt) for r in reqs} <= cat
    assert len(cat) <= tr["prompt"]["lengths"]
    for r in reqs:
        assert len(r.prompt) + r.max_new <= tr["max_seq"]
        assert tr["output"]["min"] <= r.max_new <= tr["output"]["max"]


def test_unknown_kinds_are_refused():
    from chipbench.run import driver
    tr = {**_traffic("chat_poisson"), "arrival": "burst"}
    with pytest.raises(ValueError, match="burst"):
        traffic_gen.requests(tr, 1, 1.0, 151936)
    with pytest.raises(ValueError, match="batch"):
        driver("batch")
    assert traffic_gen.requests(_traffic("chat_poisson"), 1, 1.0, 1000)


def test_train_batch_matches_program_pipeline():
    from repro.data.pipeline import SyntheticLM
    seed = 2**31 + 99
    src = SyntheticLM(vocab_size=1000, seq_len=64, global_batch=4, seed=seed)
    for step in (0, 3):
        ours = traffic_gen.train_batch(seed, step, 4, 64, 1000)
        assert np.array_equal(ours, src.batch(step)["tokens"])
    assert not np.array_equal(traffic_gen.train_batch(seed, 0, 4, 64, 1000),
                              traffic_gen.train_batch(seed, 1, 4, 64, 1000))
