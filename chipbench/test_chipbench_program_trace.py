"""The reduction of the program's spans and device programs: interval
arithmetic on made-up events, and the whole path on traces recorded on a
TPU v5e."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

from chipbench import program_trace as pt
from chipbench import trace_reduce as tr

TESTDATA = Path(__file__).resolve().parent / "testdata"
NS = 1e-9


def _trace(devices=1):
    # window 0..100 ns. Device: prefill 10-30, decode 50-70 (two ops) and
    # 95-105; idle 0-10, 30-50 and 70-95. Harness ticks 0-80 and 80-100;
    # the engine's spans nest inside them, and a sample runs past the end.
    t = tr.Trace()
    mods = {}
    for d in range(devices):
        dev = f"/device:TPU:{d}"
        t.ops[dev] = [("fusion.1", 10, 30), ("fusion.2", 50, 60),
                      ("fusion.3", 60, 70), ("fusion.2", 95, 105)]
        mods[dev] = [("serve_prefill", 10, 30), ("serve_decode", 50, 70),
                     ("serve_decode", 95, 105)]
    t.spans = [("window", 0, 100), ("tick", 0, 80), ("tick", 80, 100)]
    spans = [("serve.admit", 2, 40, {"rid": 1, "length": 5,
                                     "wait_us": 1500}),
             ("serve.prefill", 5, 32, {"rid": 1}),
             ("serve.insert", 33, 38, {"rid": 1, "slot": 0}),
             ("serve.decode", 45, 72, {"active": 1, "slots": 2}),
             ("serve.sample", 72, 79, {"finished": 0}),
             ("serve.decode", 85, 96, {"active": 2, "slots": 2}),
             ("serve.sample", 96, 110, {"finished": 1})]
    return pt.ProgramTrace(t, spans, mods)


def test_module_names_drop_jit_and_the_id():
    assert pt.module_name("jit_serve_decode(1183958075)") == "serve_decode"
    assert pt.module_name("jit__lambda(11839580750396842669)") == "_lambda"
    assert pt.module_name("jit_train_step") == "train_step"
    assert pt.module_name("copy") == "copy"


@pytest.mark.parametrize("devices", [1, 2])
def test_program_spans_modules_and_gaps(devices):
    out = pt.reduce(_trace(devices))
    assert [s[0] for s in out["program_spans"]] == [
        "serve.admit", "serve.prefill", "serve.insert", "serve.decode",
        "serve.sample", "serve.decode"]      # the last sample ends outside
    assert out["program_spans"][0][3] == {"rid": 1, "length": 5,
                                          "wait_us": 1500}
    runs = out["modules"]
    assert runs["serve_prefill"] == pytest.approx([1, 20 * NS])
    # 50-70 and 95-105 clipped to 95-100
    assert runs["serve_decode"] == pytest.approx([2, 25 * NS])
    gaps = dict(out["program_gaps"])
    want = {"tick": 2 + 5 + 1 + 5, "serve.admit": 3 + 1 + 2,
            "serve.prefill": 5 + 2, "serve.insert": 5,
            "serve.decode": 5 + 2 + 10, "serve.sample": 7}
    assert gaps == pytest.approx({k: v * NS for k, v in want.items()})
    # the harness's split of the same idle time is unchanged
    assert dict(out["idle_gaps"]) == pytest.approx({"tick": 55 * NS})
    idle = out["window_s"] - out["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle)
    assert sum(v for _, v in out["idle_gaps"]) == pytest.approx(idle)


def test_numbers():
    n = pt.numbers(pt.reduce(_trace()))
    assert n == pytest.approx({
        "chat.queue_wait_ms": 1.5,
        "chat.prefill_ms": 27e-6,
        "chat.decode_device_ms": 1e3 * 25 * NS / 2,
        "chat.slot_occupancy": (50 + 100) / 2,
        "chat.admit_idle": 100 * (6 + 7 + 5) / 100,
        "chat.sample_idle": 7.0})
    assert pt.numbers(None) == {}


def test_train_numbers_and_no_serve_numbers():
    t = tr.Trace()
    t.ops["/device:TPU:0"] = [("fusion.1", 10, 90)]
    t.spans = [("window", 0, 100), ("step", 0, 100), ("batch", 1, 4)]
    spans = [("train.batch", 0, 5, {"step": 3}),
             ("train.dispatch", 5, 9, {"step": 3, "attempt": 0}),
             ("train.sync", 9, 95, {"step": 3})]
    out = pt.reduce(pt.ProgramTrace(t, spans, {}))
    gaps = dict(out["program_gaps"])
    # idle 0-10 and 90-100: batch inside train.batch, then the rest of
    # train.batch, dispatch, sync, and the step's tail
    assert gaps == pytest.approx({"batch": 3 * NS, "train.batch": 2 * NS,
                                  "train.dispatch": 4 * NS,
                                  "train.sync": 6 * NS, "step": 5 * NS})
    assert pt.numbers(out) == pytest.approx({"train.dispatch_ms": 4e-6})


def test_innermost_span_however_deep_the_nesting():
    # three admissions of three spans each inside one tick, then time in
    # the tick after them: the harness reduction looks back only a few
    # spans and would name that time by no span
    spans = [("tick", 0, 100)]
    for k in range(3):
        a = 10 * k
        spans += [("serve.admit", a, a + 9), ("serve.prefill", a + 1, a + 5),
                  ("serve.insert", a + 6, a + 8)]
    segs = pt.innermost_segments(spans, 0, 100)
    assert segs[-1] == ("tick", 29, 100)
    assert segs[:4] == [("serve.admit", 0, 1), ("serve.prefill", 1, 5),
                        ("serve.admit", 5, 6), ("serve.insert", 6, 8)]
    assert tr.named_segments(sorted(spans, key=lambda sp: sp[1]),
                             0, 100)[-1] == (tr.NO_SPAN, 29, 100)
    assert pt.innermost_segments([], 0, 10) == [(tr.NO_SPAN, 0, 10)]


def test_no_device_ops_gives_nothing():
    assert pt.reduce(pt.ProgramTrace(tr.Trace())) is None


def test_harness_only_trace_splits_idle_as_before():
    # the recorded train trace holds no program span: the new split is the
    # old one, and every key the harness reads keeps its value
    t = pt.load(TESTDATA / "small_trace.xplane.pb")
    assert t.spans == []
    old = tr.reduce(tr.load(TESTDATA / "small_trace.xplane.pb"))
    out = pt.reduce(t)
    for k, v in old.items():
        assert out[k] == v
    assert dict(out["program_gaps"]) == pytest.approx(dict(old["idle_gaps"]))
    assert "_lambda" in out["modules"]


def test_recorded_chat_trace(tmp_path):
    # 0.32 s of the chat cell's traced window on a TPU v5e, 20 s into it
    # (program_trace.py --workload qwen3-0.6b.chat --delay 20
    # --trace-seconds 0.3 --keep), trimmed to what the reductions read (the
    # device's XLA Ops and XLA Modules lines without event stats, op names
    # cut before " = ", the host's python thread) and gzipped; both
    # reductions read the trimmed file as they read the recording
    path = tmp_path / "program_trace.xplane.pb"
    path.write_bytes(gzip.decompress(
        (TESTDATA / "program_trace.xplane.pb.gz").read_bytes()))
    out = pt.reduce(pt.load(path))
    assert out["modules"]["serve_decode"][0] >= 5
    assert out["modules"]["serve_prefill"][0] >= 1
    names = {s[0] for s in out["program_spans"]}
    assert {"serve.admit", "serve.prefill", "serve.insert", "serve.decode",
            "serve.sample"} <= names
    gaps = dict(out["program_gaps"])
    assert {"serve.sample", "serve.decode", "serve.prefill"} <= set(gaps)
    idle = out["window_s"] - out["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-9)
    assert sum(v for _, v in out["idle_gaps"]) == pytest.approx(idle,
                                                                rel=1e-9)
    n = pt.numbers(out)
    assert 0 < n["chat.slot_occupancy"] <= 100
    assert 0 < n["chat.admit_idle"] + n["chat.sample_idle"] < 100
    assert 15 < n["chat.decode_device_ms"] < 30
