"""Architecture registry: ``get_config(arch_id)``.

One module per assigned architecture under ``repro.configs``; this registry
imports them lazily and exposes the arch ids for ``--arch``.
"""

from __future__ import annotations

import importlib

from .base import ModelConfig

ARCH_IDS = [
    "whisper_base",
    "phi3_vision_4p2b",
    "llama3p2_3b",
    "granite_8b",
    "rwkv6_3b",
    "granite_34b",
    "jamba_v0p1_52b",
    "kimi_k2_1t_a32b",
    "mistral_nemo_12b",
    "deepseek_moe_16b",
    "deepseek_v2_lite",
    # paper's own experiment model (Section J / K.5)
    "nanogpt_paper",
]

# canonical dashed names from the assignment card -> module name
ALIASES = {
    "whisper-base": "whisper_base",
    "phi-3-vision-4.2b": "phi3_vision_4p2b",
    "llama3.2-3b": "llama3p2_3b",
    "granite-8b": "granite_8b",
    "rwkv6-3b": "rwkv6_3b",
    "granite-34b": "granite_34b",
    "jamba-v0.1-52b": "jamba_v0p1_52b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "deepseek-v2-lite-ep8": "deepseek_v2_lite",
    "nanogpt-paper": "nanogpt_paper",
}


def get_config(arch: str) -> ModelConfig:
    mod_name = ALIASES.get(arch, arch).replace("-", "_").replace(".", "p")
    if mod_name not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ALIASES)}")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.CONFIG


def all_configs() -> dict:
    return {a: get_config(a) for a in ARCH_IDS}
