"""The trace reduction: interval arithmetic on made-up events, and the
whole path on a small trace recorded on a TPU v5e."""
from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

FIXTURE = Path(__file__).resolve().parent / "testdata" / \
    "small_trace.xplane.pb"


def _trace():
    # window 0..100 ns; device 0: fusion 10-30, all-reduce 25-45 (5 ns
    # hidden under the fusion), fusion 60-70; device 1: fusion 0-50
    t = tr.Trace()
    t.ops["/device:TPU:0"] = [("fusion.1", 10, 30), ("all-reduce.2", 25, 45),
                              ("fusion.1", 60, 70)]
    t.ops["/device:TPU:1"] = [("fusion.1", 0, 50)]
    t.spans = [("window", 0, 100), ("step", 0, 55), ("batch", 0, 8),
               ("step", 55, 100)]
    return t


def test_union_and_subtract():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_reduce_busy_collectives_and_gaps():
    out = tr.reduce(_trace())
    ns = 1e-9
    assert out["window_s"] == pytest.approx(100 * ns)
    # device 0 busy 10-45 and 60-70 = 45; device 1 busy 50
    assert out["busy_s"] == pytest.approx(47.5 * ns)
    assert out["collective_s"] == pytest.approx(10 * ns)     # (20 + 0) / 2
    assert out["exposed_collective_s"] == pytest.approx(7.5 * ns)
    gaps = dict(out["idle_gaps"])
    # device 0 idle 0-10 (0-8 in batch, 8-10 in the step round it), 45-60
    # (step 0-55, then step 55-100), 70-100 (step); device 1 idle 50-100
    # (step); each halved over 2 devices
    assert gaps["batch"] == pytest.approx(8 / 2 * ns)
    assert gaps["step"] == pytest.approx((2 + 15 + 30 + 50) / 2 * ns)
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx((30 + 50) / 2 * ns)


def test_loops_are_not_ranked_or_counted_twice():
    # a while op 0-100 holds its body's ops 10-40 and 50-90; a second
    # while 100-120 holds nothing and is an op of its own
    t = tr.Trace()
    t.ops["/device:TPU:0"] = [("while.1", 0, 100), ("fusion.2", 10, 40),
                              ("fusion.3", 50, 90), ("while.4", 100, 120)]
    t.spans = [("window", 0, 120), ("step", 0, 95), ("batch", 95, 120)]
    out = tr.reduce(t)
    ns = 1e-9
    ops = dict(out["device_ops"])
    assert "while.1" not in ops
    assert ops["while.4"] == pytest.approx(20 * ns)
    assert out["busy_s"] == pytest.approx((30 + 40 + 20) * ns)
    gaps = dict(out["idle_gaps"])
    # idle 0-10, 40-50 and 90-100: 90-95 in step, 95-100 in batch
    assert gaps["step"] == pytest.approx(25 * ns)
    assert gaps["batch"] == pytest.approx(5 * ns)


def test_no_device_ops_gives_nothing():
    assert tr.reduce(tr.Trace()) is None


def test_recorded_tpu_trace():
    t = tr.load(FIXTURE)
    assert len(t.ops) == 1 and next(iter(t.ops)).startswith("/device:TPU")
    names = {n for n, _, _ in t.spans}
    assert {"window", "step", "batch"} <= names
    out = tr.reduce(t)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["collective_s"] == 0
    assert {"fusion", "copy-done"} <= {n for n, _ in out["device_ops"]}
    gaps = dict(out["idle_gaps"])
    assert "batch" in gaps          # the host slept in bench.batch
    lo, hi = tr.window_of(t)
    ops = [o for ops in t.ops.values() for o in ops]
    assert all(lo - 1e6 < s and e < hi + 1e6 for _, s, e in ops)
