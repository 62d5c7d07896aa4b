"""Production meshes.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked on first jax init, and the
512-device dry-run must set XLA_FLAGS before that happens).

Every axis is ``AxisType.Auto``: the model code places arrays with
``with_sharding_constraint`` and lets GSPMD propagate the rest, which
``jax.make_mesh``'s default ``Explicit`` axes refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Target machine: TPU v5e pods, 256 chips each (dry-run only).

    single-pod  (16, 16)    axes (data, model)
    multi-pod   (2, 16, 16) axes (pod, data, model) — "pod" is folded into
                the data-parallel group (gradient all-reduce crosses pods;
                everything else stays pod-local).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over the first data*model devices that exist."""
    return _auto_mesh((data, model), ("data", "model"))
