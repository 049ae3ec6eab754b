"""Config registry: ``--arch <id>`` ids -> ModelConfig factories.

The ten assigned architectures (public-literature pool) plus the paper's
own models.
"""
from __future__ import annotations

from repro.models.config import ModelConfig

_ARCH_MODULES = {
    "zamba2-2.7b": "zamba2_2p7b",
    "xlstm-350m": "xlstm_350m",
    "mixtral-8x7b": "mixtral_8x7b",
    "musicgen-large": "musicgen_large",
    "starcoder2-3b": "starcoder2_3b",
    "phi3-mini-3.8b": "phi3_mini_3p8b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b",
    "deepseek-7b": "deepseek_7b",
    "chameleon-34b": "chameleon_34b",
    "tinyllama-1.1b": "tinyllama_1p1b",
    "dndm-text8": "dndm_text8",
    "dndm-mt": "dndm_mt",
    "sdar-30b-a3b": "sdar_30b_a3b",
}

ASSIGNED_ARCHS = tuple(list(_ARCH_MODULES)[:10])


def get(name: str) -> ModelConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_ARCH_MODULES)}")
    import importlib
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return mod.get_config()


def list_archs() -> list[str]:
    return list(_ARCH_MODULES)


__all__ = ["get", "list_archs", "ASSIGNED_ARCHS"]
