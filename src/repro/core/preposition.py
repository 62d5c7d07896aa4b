"""Prepositioning, adapted to TPU pods (paper T4).

The paper copies whole application installs onto every node's local disk so
process start-up never touches central Lustre. On a TPU pod the expensive
artifact that stands between "user hits enter" and "first step executes" is
not a binary on disk — it is the **XLA executable** (minutes of compile for
a big model) and the **materialized sharded weights**. Prepositioning

  CompileCacheWarmer   pre-lowers + pre-compiles every (arch × shape × mesh)
                       program the interactive session might launch and
                       keeps the executables keyed in memory — the analogue
                       of the five MATLAB installs on local disk,
  WeightPrepositioner  initializes (or restores) the sharded param/optimizer
                       trees ahead of the session,

so that an interactive sweep of N models launches with ZERO compiles and
ZERO H2D weight transfers in the interactive loop — the same insight as the
paper: move the heavy artifact next to the compute *before* the user is
waiting.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding

from repro.configs.base import ArchConfig, ShapeConfig

CacheKey = Tuple[str, str, Tuple[Tuple[str, int], ...]]


def cache_key(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh) -> CacheKey:
    return (cfg.name, shape.name, tuple(sorted(dict(mesh.shape).items())))


@dataclass
class WarmEntry:
    compiled: Any                  # jax CompiledFunction
    lower_s: float                 # time spent lowering (tracing)
    compile_s: float               # time spent in XLA backend compile
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None


class CompileCacheWarmer:
    """Pre-compile programs for an interactive session.

    ``warm(...)`` is the slow path run *before* the session (the rsync of
    MATLAB installs); ``get(...)`` is the interactive fast path and never
    compiles — a miss raises, because a compile inside the interactive loop
    is precisely the failure mode the paper engineered away.
    """

    def __init__(self):
        self._cache: Dict[CacheKey, WarmEntry] = {}
        self.stats = {"warms": 0, "hits": 0, "misses": 0}

    def __contains__(self, key: CacheKey) -> bool:
        return key in self._cache

    def warm(self, cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh,
             build: Callable[[], Any]) -> WarmEntry:
        """build() -> (fn, in_shardings, out_shardings, abstract_args)."""
        key = cache_key(cfg, shape, mesh)
        if key in self._cache:
            return self._cache[key]
        fn, in_sh, out_sh, args = build()
        wrap = lambda s: jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, x), s)
        t0 = time.monotonic()
        with mesh:
            lowered = jax.jit(fn, in_shardings=wrap(in_sh),
                              out_shardings=wrap(out_sh)).lower(*args)
        t1 = time.monotonic()
        compiled = lowered.compile()
        t2 = time.monotonic()
        cost = {}
        try:
            cost = compiled.cost_analysis() or {}
        except Exception:
            pass
        entry = WarmEntry(compiled, t1 - t0, t2 - t1,
                          flops=cost.get("flops"),
                          bytes_accessed=cost.get("bytes accessed"))
        self._cache[key] = entry
        self.stats["warms"] += 1
        return entry

    def get(self, cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh) -> WarmEntry:
        key = cache_key(cfg, shape, mesh)
        if key not in self._cache:
            self.stats["misses"] += 1
            raise KeyError(
                f"compile cache cold for {key} — warm() it before the "
                f"interactive session (paper T4)")
        self.stats["hits"] += 1
        return self._cache[key]


class WeightPrepositioner:
    """Materialize sharded params/opt-state ahead of the interactive session.

    Keyed by (arch, mesh, seed). For a sweep of N models that share the base
    architecture, the prepositioned tree is initialized ONCE and cheap
    per-member variation (a fresh RNG fold, an LR change) happens inside the
    already-compiled program.
    """

    def __init__(self):
        self._store: Dict[Tuple[str, Tuple[Tuple[str, int], ...], int], Any] = {}

    def preposition(self, cfg: ArchConfig, mesh: Mesh, seed: int,
                    init: Callable[[], Any]):
        key = (cfg.name, tuple(sorted(dict(mesh.shape).items())), seed)
        if key not in self._store:
            self._store[key] = init()
        return self._store[key]

    def get(self, cfg: ArchConfig, mesh: Mesh, seed: int):
        key = (cfg.name, tuple(sorted(dict(mesh.shape).items())), seed)
        if key not in self._store:
            raise KeyError(f"weights not prepositioned for {key}")
        return self._store[key]
