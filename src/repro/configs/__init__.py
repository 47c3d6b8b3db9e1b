"""Architecture registry: ``--arch <id>`` -> ModelConfig."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.config import ModelConfig, reduced

_MODULES: Dict[str, str] = {
    "whisper-large-v3": "whisper_large_v3",
    "olmo-1b": "olmo_1b",
    "mamba2-780m": "mamba2_780m",
    "qwen3-8b": "qwen3_8b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe",
    "internlm2-20b": "internlm2_20b",
    "gemma3-12b": "gemma3_12b",
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "zamba2-7b": "zamba2_7b",
    "deepseek-v3-671b": "deepseek_v3_671b",
}

#: the assigned architecture pool: the routing corpus's fleet
#: (data/routerbench.py), the dry-run's and the trainer's choices
ARCH_IDS: List[str] = list(_MODULES)

# published members the benchmark serves besides the pool
_MODULES.update({
    "moonlight-16b-a3b": "moonlight_16b_a3b",
    "moonlight-16b-a3b-ep8": "moonlight_16b_a3b",
})
#: every registered configuration
ALL_IDS: List[str] = list(_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {ALL_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return next(c for c in vars(mod).values()
                if isinstance(c, ModelConfig) and c.name == arch)


def get_reduced_config(arch: str, **overrides) -> ModelConfig:
    """Tiny same-family variant for CPU smoke tests."""
    return reduced(get_config(arch), **overrides)
