"""Architecture config registry: ``get_config("yi-6b")`` etc."""
from __future__ import annotations

import importlib
from typing import Dict

from repro.configs.base import (
    LM_SHAPES,
    LONG_CONTEXT_ARCHS,
    SHAPES_BY_NAME,
    ModelConfig,
    ShapeConfig,
    applicable_shapes,
)

_ARCH_MODULES: Dict[str, str] = {
    "yi-6b": "repro.configs.yi_6b",
    "nemotron-4-15b": "repro.configs.nemotron_4_15b",
    "qwen2.5-3b": "repro.configs.qwen2_5_3b",
    "qwen2.5-32b": "repro.configs.qwen2_5_32b",
    "zamba2-1.2b": "repro.configs.zamba2_1_2b",
    "chameleon-34b": "repro.configs.chameleon_34b",
    "moonlight-16b-a3b": "repro.configs.moonlight_16b_a3b",
    "qwen2-moe-a2.7b": "repro.configs.qwen2_moe_a2_7b",
    "mamba2-130m": "repro.configs.mamba2_130m",
    "whisper-tiny": "repro.configs.whisper_tiny",
}

ARCH_NAMES = tuple(_ARCH_MODULES)


def get_config(name: str) -> ModelConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; choose from {ARCH_NAMES}")
    return importlib.import_module(_ARCH_MODULES[name]).CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {n: get_config(n) for n in ARCH_NAMES}


def get_shape(name: str) -> ShapeConfig:
    return SHAPES_BY_NAME[name]


__all__ = [
    "ARCH_NAMES",
    "LM_SHAPES",
    "LONG_CONTEXT_ARCHS",
    "ModelConfig",
    "ShapeConfig",
    "all_configs",
    "applicable_shapes",
    "get_config",
    "get_shape",
]
