"""Assigned architecture configs (public-literature dims) + reduced smoke
variants.

Every config is selectable via ``--arch <id>`` in the launchers; ``REGISTRY``
maps id -> full ModelConfig, ``smoke_config(id)`` returns the reduced
same-family variant used by CPU tests (small layers/width, few experts, tiny
vocab).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List

from ..models.config import ModelConfig

ARCH_IDS: List[str] = [
    "smollm_360m",
    "granite_8b",
    "qwen3_4b",
    "starcoder2_15b",
    "llama4_scout_17b_a16e",
    "moonshot_v1_16b_a3b",
    "falcon_mamba_7b",
    "hubert_xlarge",
    "llava_next_mistral_7b",
    "zamba2_1p2b",
]

_ALIASES = {
    "smollm-360m": "smollm_360m",
    "granite-8b": "granite_8b",
    "qwen3-4b": "qwen3_4b",
    "starcoder2-15b": "starcoder2_15b",
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b_a3b",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "hubert-xlarge": "hubert_xlarge",
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "zamba2-1.2b": "zamba2_1p2b",
    # served, not in the dry-run matrix
    "granite-4.0-h-micro": "granite_4p0_h_micro",
}


def canonical(arch: str) -> str:
    return _ALIASES.get(arch, arch.replace("-", "_").replace(".", "p"))


def get_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f".{canonical(arch)}", __package__)
    return mod.CONFIG


def smoke_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f".{canonical(arch)}", __package__)
    return mod.SMOKE


def registry() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
