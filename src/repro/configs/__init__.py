"""Architecture registry: ``get_config("<arch-id>")`` / ``--arch <id>``."""
from __future__ import annotations

import importlib

from repro.configs.base import (  # noqa: F401
    AttentionConfig, LONG_CONTEXT_ARCHS, ModelConfig, MoEConfig, MoPConfig,
    SHAPES, ShapeConfig, SSMConfig, YarnConfig, reduce_for_smoke,
    shape_applicable,
)

_MODULES = {
    "seamless-m4t-medium": "seamless_m4t_medium",
    "qwen3-8b": "qwen3_8b",
    "minitron-4b": "minitron_4b",
    "granite-3-2b": "granite_3_2b",
    "smollm-360m": "smollm_360m",
    "zamba2-7b": "zamba2_7b",
    "rwkv6-3b": "rwkv6_3b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "mixtral-8x7b": "mixtral_8x7b",
    "paligemma-3b": "paligemma_3b",
    "mixtral-mop": "mixtral_mop",
    "deepseek-v2-lite": "deepseek_v2_lite",
}

ARCH_IDS = tuple(k for k in _MODULES if k != "mixtral-mop")


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.CONFIG


def all_cells():
    """Every runnable (arch, shape) dry-run cell — DESIGN.md §6."""
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if shape_applicable(cfg, shape):
                yield arch, shape.name
