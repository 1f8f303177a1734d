"""Config registry: one module per assigned architecture (+ the paper's own)."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ModelConfig, param_count, active_param_count  # noqa: F401
from repro.configs.shapes import SHAPES, InputShape, shapes_for  # noqa: F401

# arch id -> module name in this package
_REGISTRY = {
    "xlstm-125m":       "xlstm_125m",
    "qwen1.5-32b":      "qwen1_5_32b",
    "zamba2-7b":        "zamba2_7b",
    "qwen3-14b":        "qwen3_14b",
    "whisper-base":     "whisper_base",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "internvl2-2b":     "internvl2_2b",
    "qwen1.5-0.5b":     "qwen1_5_0_5b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "deepseek-v2-lite": "deepseek_v2_lite",
    "qwen2-moe-a2.7b":  "qwen2_moe_a2_7b",
    "resnet50":         "resnet50",   # the paper's own architecture
}

ASSIGNED_ARCHS: List[str] = [a for a in _REGISTRY if a != "resnet50"]
ALL_ARCHS: List[str] = list(_REGISTRY)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_REGISTRY)}")
    mod = importlib.import_module(f"repro.configs.{_REGISTRY[arch_id]}")
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in _REGISTRY}
