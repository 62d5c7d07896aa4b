"""Serving runtime: continuous batching over a fixed slot pool.

One jitted decode program serves B slots; requests stream in/out of slots:
  submit()  — queue a prompt
  tick()    — admit queued requests into free slots (per-request prefill,
              then ``serve_insert`` writes its cache into the slot in
              place, donating the batched cache), then one batched decode
              step for every active slot; finished sequences free slots.

Per-slot cache lengths (vectorized cache_len) make heterogeneous prompt
lengths exact, not padded-approximate. Prefill runs at each prompt's exact
length, so every distinct length compiles a program of its own (the compile
cache is prepositioned by repro.core.preposition — the paper's T4).

Each step of admit and tick is a ``jax.profiler.TraceAnnotation`` named
``repro.serve.*``, with its counters as arguments, so a profiler trace of a
running engine places them beside the device's work (README, "Tracing").
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.models import decode_step, init_cache, prefill


def make_prefill_fn(cfg: ArchConfig):
    @jax.jit
    def serve_prefill(params, tokens):
        return prefill(params, cfg, tokens)
    return serve_prefill


def make_decode_fn(cfg: ArchConfig):
    # No donation yet: the decode's layer scan reads the stacked cache as
    # ``xs`` and writes a new stacked ``ys``, which cannot share a buffer
    # with the ``xs`` it is still reading, so a donated cache would cost a
    # whole-cache copy at the loop boundary instead of saving one.
    @jax.jit
    def serve_decode(params, token, cache, cache_len):
        return decode_step(params, cfg, token, cache, cache_len)
    return serve_decode


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int = 32
    eos: int = -1
    tokens: List[int] = field(default_factory=list)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None


def make_insert_fn():
    """Writes a single-request cache (B=1) into slot ``slot`` (a traced
    int32) of the batched cache, whose buffers it donates, so each leaf is
    updated in place. Every leaf has batch at dim 1 ([L, B, ...]) by
    construction; the prefill's cache has the batched cache's shape but for
    the batch, so one program serves every slot and prompt length."""
    @functools.partial(jax.jit, donate_argnums=0)
    def serve_insert(cache, slot_cache, slot):
        def ins(big, one):
            return jax.lax.dynamic_update_slice_in_dim(
                big, one.astype(big.dtype), slot, axis=1)
        return jax.tree_util.tree_map(ins, cache, slot_cache)
    return serve_insert


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, slots: int = 8,
                 max_seq: int = 2048, greedy: bool = True, seed: int = 0):
        self.cfg, self.params = cfg, params
        self.slots = slots
        self.max_seq = max_seq
        self.greedy = greedy
        self.key = jax.random.PRNGKey(seed)
        self.cache = init_cache(cfg, slots, max_seq)
        self.cache_len = np.zeros((slots,), np.int32)
        self.active: List[Optional[Request]] = [None] * slots
        self.queue: List[Request] = []
        self.done: Dict[int, Request] = {}
        self.next_token = np.zeros((slots,), np.int32)
        self._rid = 0
        self._decode = make_decode_fn(cfg)
        self._insert = make_insert_fn()
        self._prefills: Dict[int, Any] = {}   # per-length jitted prefill
        self.stats = {"decode_steps": 0, "prefills": 0, "inserts": 0,
                      "inserts_in_place": 0}

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new: int = 32, eos: int = -1) -> int:
        rid = self._rid
        self._rid += 1
        self.queue.append(Request(rid, np.asarray(prompt, np.int32),
                                  max_new, eos, submitted_at=time.monotonic()))
        return rid

    def _prefill_fn(self, length: int):
        if length not in self._prefills:
            cfg = self.cfg

            @jax.jit
            def serve_prefill(params, tokens):
                return prefill(params, cfg, tokens,
                               pad=self.max_seq - tokens.shape[1])
            self._prefills[length] = serve_prefill
        return self._prefills[length]

    def _admit(self):
        for slot in range(self.slots):
            if self.active[slot] is not None or not self.queue:
                continue
            req = self.queue.pop(0)
            L = len(req.prompt)
            wait_us = int(1e6 * (time.monotonic() - req.submitted_at))
            with TraceAnnotation("repro.serve.admit", rid=req.rid, length=L,
                                 wait_us=wait_us):
                with TraceAnnotation("repro.serve.prefill", rid=req.rid):
                    # exact-length prefill: one compiled program per
                    # distinct prompt length; the compile cache is
                    # prepositioned ahead of the interactive session
                    # (repro.core.preposition, paper T4).
                    toks = req.prompt[None, :]
                    logits, c1 = self._prefill_fn(L)(self.params,
                                                     jnp.asarray(toks))
                    nxt = int(jnp.argmax(logits[0]))
                req.tokens.append(nxt)
                req.first_token_at = time.monotonic()
                self.stats["prefills"] += 1
                if nxt == req.eos or len(req.tokens) >= req.max_new:
                    # finished at the first token: never occupies a slot
                    req.done_at = time.monotonic()
                    self.done[req.rid] = req
                    continue
                with TraceAnnotation("repro.serve.insert", rid=req.rid,
                                     slot=slot) as span:
                    old = jax.tree_util.tree_leaves(self.cache)
                    self.cache = self._insert(self.cache, c1, np.int32(slot))
                    # the donation took where every old buffer is gone
                    in_place = int(all(x.is_deleted() for x in old))
                    del old
                    span.set_metadata(in_place=in_place)
                self.stats["inserts"] += 1
                self.stats["inserts_in_place"] += in_place
                self.active[slot] = req
                self.cache_len[slot] = L
                self.next_token[slot] = nxt

    # ------------------------------------------------------------------
    def tick(self):
        """Admit + one decode step across all active slots."""
        self._admit()
        active = sum(r is not None for r in self.active)
        if not active:
            return False
        with TraceAnnotation("repro.serve.decode", active=active,
                             slots=self.slots):
            logits, self.cache = self._decode(
                self.params, jnp.asarray(self.next_token), self.cache,
                jnp.asarray(self.cache_len))
        self.stats["decode_steps"] += 1
        with TraceAnnotation("repro.serve.sample") as span:
            if self.greedy:
                nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            else:
                self.key, sub = jax.random.split(self.key)
                nxt = np.asarray(jax.random.categorical(sub, logits),
                                 np.int32)
            finished = 0
            for slot, req in enumerate(self.active):
                if req is None:
                    continue
                self.cache_len[slot] += 1
                tok = int(nxt[slot])
                req.tokens.append(tok)
                self.next_token[slot] = tok
                if tok == req.eos or len(req.tokens) >= req.max_new:
                    req.done_at = time.monotonic()
                    self.done[req.rid] = req
                    self.active[slot] = None
                    self.cache_len[slot] = 0
                    finished += 1
            span.set_metadata(finished=finished)
        return True

    def run(self, max_ticks: int = 10_000):
        while (self.queue or any(r is not None for r in self.active)) \
                and max_ticks > 0:
            self.tick()
            max_ticks -= 1
        return self.done
