"""Architecture registry: ``--arch <id>`` lookup for every assigned config."""
from __future__ import annotations

from repro.configs.base import ModelConfig, INPUT_SHAPES, InputShape

_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    assert cfg.name not in _REGISTRY, f"duplicate arch {cfg.name}"
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    _load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> list[str]:
    _load_all()
    return sorted(_REGISTRY)


def get_shape(name: str) -> InputShape:
    return INPUT_SHAPES[name]


_LOADED = False


def _load_all():
    global _LOADED
    if _LOADED:
        return
    # import every config module for its register() side effect
    from repro.configs import (  # noqa: F401
        whisper_large_v3, llava_next_34b, jamba_v0_1_52b, grok_1_314b,
        starcoder2_3b, yi_9b, xlstm_1_3b, kimi_k2_1t_a32b, gemma2_2b,
        phi3_mini_3_8b, a3c_atari, moonlight_16b_a3b)
    _LOADED = True
