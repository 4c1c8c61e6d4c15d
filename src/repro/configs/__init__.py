"""Architecture registry: --arch <id> resolution."""
from __future__ import annotations

import importlib
from typing import Dict

from .base import (INPUT_SHAPES, DECODE_32K, LONG_500K, PREFILL_32K,
                   TRAIN_4K, InputShape, ModelConfig)

_MODULES = {
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "llama3-405b": "llama3_405b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "whisper-small": "whisper_small",
    "minitron-4b": "minitron_4b",
    "glm4-9b": "glm4_9b",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "chatglm3-6b": "chatglm3_6b",
    "mamba2-370m": "mamba2_370m",
    "pixtral-12b": "pixtral_12b",
    # the paper's own workloads
    "internvl3-2b": "internvl3_2b",
    "qwen3vl-8b": "qwen3vl_8b",
    "kimi-vl-a3b": "kimi_vl_a3b",
}

ASSIGNED_ARCHS = list(_MODULES)[:10]
ALL_ARCHS = list(_MODULES)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ALL_ARCHS}")
    mod = importlib.import_module(f".{_MODULES[arch_id]}", __package__)
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ALL_ARCHS}


__all__ = ["ModelConfig", "InputShape", "INPUT_SHAPES", "TRAIN_4K",
           "PREFILL_32K", "DECODE_32K", "LONG_500K", "get_config",
           "all_configs", "ASSIGNED_ARCHS", "ALL_ARCHS"]
