"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel is a jit'd, BlockSpec-tiled pl.pallas_call that compiles for
the TPU v5e, with a pure-jnp oracle in ref.py. The CPU tests
(tests/test_kernels.py) pass ``interpret=True`` explicitly; nothing falls
back to interpret mode on its own.
"""
from . import flash_attention, ref, rmsnorm, ssd_scan  # noqa: F401
