"""Drives the program's ServeEngine for traffic of kind ``serve``.

Arrivals are an open loop (``traffic_gen.requests``): requests are
submitted when due, whatever the engine is doing, and each is timed from
when it was due.

The harness stamps each output token when it first sees it, after the
``tick()`` that produced it returns (the tick ends in a host copy of the
picked tokens, so the device's work is done by then).
"""
from __future__ import annotations

import gc
import sys
import time
from dataclasses import dataclass, field
from typing import List

import jax
import numpy as np

from chipbench import flops, reference, spec, traffic_gen, weights
from chipbench.drive_train import _delete, max_memory


@dataclass
class Track:
    req: object          # the engine's Request
    due: float           # host time the request was due
    length: int          # prompt length
    seen: int = 0
    last: float = 0.0
    itl: List[float] = field(default_factory=list)


def _stamp(run, c, tracks, t, t_end):
    """Record the tokens that appeared in the last tick; returns how many."""
    new_total, work = 0, 0.0
    for tk in tracks:
        n = len(tk.req.tokens)
        if n == tk.seen:
            continue
        for j in range(tk.seen + 1, n + 1):      # output token j (1-based)
            if j == 1:
                run.samples.setdefault("ttft_s", []).append(t - tk.due)
                work += flops.prefill(c, tk.length)
            else:
                work += flops.decode_token(c, tk.length + j - 1)
                if t <= t_end:
                    tk.itl.append(t - tk.last if j == tk.seen + 1 else 0.0)
        new_total += n - tk.seen
        tk.seen, tk.last = n, t
    run.work.append((t, work))
    return new_total


def setup(run, exact=True):
    """The engine with the benchmark's weights, every shape warmed."""
    from repro.serve.engine import ServeEngine

    c, tr = run.cell.config, run.cell.traffic
    params = spec.arch(c).to_program(
        weights.maker(c)(weights.seed_key(run.seed)))
    eng = ServeEngine(spec.arch_config(c, exact), params, slots=tr["slots"],
                      max_seq=tr["max_seq"])
    # one request of every catalog length (each its own prefill program),
    # two tokens each, so the decode program runs too
    rng = np.random.default_rng([run.seed, 7])
    for length in np.unique(traffic_gen.prompt_catalog(tr)):
        eng.submit(rng.integers(0, c["vocab_size"], int(length),
                                dtype=np.int32), max_new=2)
    eng.run()
    return eng, params


def window(run, eng, seconds, tracer, compiles, t_start):
    """The measured window, then the drain until every request due in it
    has its first token. Returns (tracks, failed)."""
    c, tr = run.cell.config, run.cell.traffic
    reqs = traffic_gen.requests(tr, run.seed, seconds, c["vocab_size"])
    tracks: List[Track] = []
    live: List[Track] = []
    late: List[float] = []

    def submit(r, due):
        eng.submit(r.prompt, max_new=r.max_new)
        tk = Track(eng.queue[-1], due, len(r.prompt))
        tracks.append(tk)
        live.append(tk)

    def tick(t_end):
        with run.span("tick"):
            eng.tick()
        t = time.monotonic()
        n = _stamp(run, c, live, t, t_end)
        live[:] = [tk for tk in live if tk.req.done_at is None]
        return t, n

    compiles.active = True
    tracer.start()
    t0 = t = time.monotonic()
    run.setup_s = t0 - t_start
    t_end = t0 + seconds
    i, out_tokens = 0, 0
    while t < t_end:
        while i < len(reqs) and t0 + reqs[i].due_s <= t:
            late.append(t - (t0 + reqs[i].due_s))
            submit(reqs[i], t0 + reqs[i].due_s)
            i += 1
        if eng.queue or live:
            t, n = tick(t_end)
            out_tokens += n
            tracer.after()
        else:
            nxt = t0 + reqs[i].due_s if i < len(reqs) else t_end
            time.sleep(max(0.0, min(nxt, t_end) - time.monotonic()))
            t = time.monotonic()
    tracer.stop()
    compiles.active = False
    run.window = (t0, t)
    run.counters["window_out_tokens"] = out_tokens
    run.counters["window_compiles"] = compiles.n
    run.counters["backlog_at_close"] = len(eng.queue)
    run.samples["itl_s"] = [g for tk in tracks for g in tk.itl]
    if late:
        print(f"generator lateness: mean {1e3 * np.mean(late):.3f} ms, "
              f"max {1e3 * max(late):.3f} ms over {len(late)} requests",
              file=sys.stderr)

    drain_end = time.monotonic() + tr["drain_s"]
    while (any(tk.seen == 0 for tk in tracks)
           and time.monotonic() < drain_end):
        tick(t_end)
    failed = sum(tk.seen == 0 for tk in tracks)
    return tracks, failed


def drive(run, seconds, tracer, compiles, t_start, exact=True):
    tr = run.cell.traffic
    eng, params = setup(run, exact)
    tracks, failed = window(run, eng, seconds, tracer, compiles, t_start)
    done = [tk for tk in tracks if tk.req.done_at is not None]
    if len(done) < tr["check_requests"]:
        # a short window: also check what requests in flight served so far
        done = [tk for tk in tracks if tk.seen > 0]
    readings = [(np.asarray(tk.req.prompt), list(tk.req.tokens))
                for tk in _sample(done, tr, run.seed)]
    memory = max_memory(jax.devices()[:run.chips])
    _delete((eng.cache, params))
    del eng, params
    gc.collect()
    return readings, len(tracks), failed, memory


def _sample(done, tr, seed):
    """The longest request, then others drawn from the seed, until the
    sample holds ``check_tokens`` served tokens and ``check_requests``
    requests."""
    if not done:
        return []
    done = sorted(done, key=lambda tk: (-len(tk.req.tokens), tk.due))
    rest = [done[j] for j in
            np.random.default_rng([seed, 3]).permutation(len(done) - 1) + 1]
    pick = [done[0]]
    for tk in rest:
        if (sum(len(p.req.tokens) for p in pick) >= tr["check_tokens"]
                and len(pick) >= tr["check_requests"]):
            break
        pick.append(tk)
    return pick


def numbers(run, readings, control=False):
    """``served_gap``: the widest gap by which a served token's reference
    logit lies below the reference's best at its position, in units of
    that position's spread of reference logits (their standard deviation,
    so that the number does not scale with the weights). With
    ``control``, also ``control_gap``: the same for the token that the fp8
    control puts first at each of those positions."""
    c, tr = run.cell.config, run.cell.traffic
    w = weights.maker(c)(weights.seed_key(run.seed))
    S, P = tr["max_seq"], tr["output"]["max"]
    gap, ctrl = [0.0], [0.0]
    if not readings:
        return {"served_gap": float("nan"), "control_gap": float("nan")}
    for prompt, served in readings:
        L, n = len(prompt), len(served)
        seq = np.zeros((1, S), np.int32)
        seq[0, :L + n - 1] = np.concatenate([prompt, served[:-1]])
        pos = np.zeros(P, np.int32)
        pos[:n] = np.arange(L - 1, L - 1 + n)
        tok = np.zeros(P, np.int32)
        tok[:n] = served
        with jax.default_matmul_precision("highest"):
            g, k = reference.served_gaps(w, reference.items(c), seq, pos,
                                         tok, control)
        gap.append(float(np.max(np.asarray(g)[:n])))
        ctrl.append(float(np.max(np.asarray(k)[:n])))
    out = {"served_gap": max(gap),
           "served_tokens": sum(len(s) for _, s in readings)}
    if control:
        out["control_gap"] = max(ctrl)
    return out
