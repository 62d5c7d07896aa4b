"""Assigned architecture registry: ``get_config(name)`` / ``--arch <id>``."""
from __future__ import annotations

import importlib

from .base import ArchConfig, ShapeConfig, SHAPES, shape_applicable  # noqa: F401

ARCH_IDS = [
    "qwen3_14b",
    "nemotron_4_340b",
    "qwen3_0_6b",
    "qwen2_1_5b",
    "xlstm_1_3b",
    "zamba2_2_7b",
    "mixtral_8x22b",
    "moonshot_v1_16b_a3b",
    "qwen2_vl_7b",
    "whisper_small",
]

# Canonical dashed ids from the assignment table -> module name
_ALIASES = {i.replace("_", "-"): i for i in ARCH_IDS}
_ALIASES.update({
    "qwen3-14b": "qwen3_14b",
    "nemotron-4-340b": "nemotron_4_340b",
    "qwen3-0.6b": "qwen3_0_6b",
    "qwen2-1.5b": "qwen2_1_5b",
    "xlstm-1.3b": "xlstm_1_3b",
    "zamba2-2.7b": "zamba2_2_7b",
    "mixtral-8x22b": "mixtral_8x22b",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b_a3b",
    "moonlight-16b-a3b": "moonshot_v1_16b_a3b",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "whisper-small": "whisper_small",
})


# one chip's share of a deployment, by name -> (module, attribute)
_SHARES = {"moonlight-16b-a3b.chip": ("moonshot_v1_16b_a3b", "CHIP")}


def get_config(name: str) -> ArchConfig:
    if name in _SHARES:
        mod_name, attr = _SHARES[name]
    else:
        mod_name, attr = _ALIASES.get(name, name), "CONFIG"
    mod_name = mod_name.replace("-", "_").replace(".", "_")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return getattr(mod, attr)


def all_configs():
    return {i: get_config(i) for i in ARCH_IDS}
