"""Architecture registry: the pool's members + the paper's own router
config. Each module defines CONFIG (exact assigned spec)."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.common.config import ModelConfig

ARCH_IDS: List[str] = [
    "granite_moe_1b_a400m",
    "gemma3_4b",
    "mamba2_130m",
    "whisper_medium",
    "qwen3_moe_30b_a3b",
    "jamba_1_5_large_398b",
    "mistral_large_123b",
    "llama3_2_3b",
    "mistral_nemo_12b",
    "llama3_2_vision_11b",
    "granite_4_0_h_small",
]

_ALIASES = {
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "gemma3-4b": "gemma3_4b",
    "mamba2-130m": "mamba2_130m",
    "whisper-medium": "whisper_medium",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "mistral-large-123b": "mistral_large_123b",
    "llama3.2-3b": "llama3_2_3b",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "llama-3.2-vision-11b": "llama3_2_vision_11b",
    "granite-4.0-h-small": "granite_4_0_h_small",
}


def get_config(arch: str) -> ModelConfig:
    arch = _ALIASES.get(arch, arch).replace("-", "_").replace(".", "_")
    if arch not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ALIASES)}")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
