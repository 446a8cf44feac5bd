"""Architecture config registry: ``get_config("qwen2.5-32b")`` etc."""
from __future__ import annotations

from repro.configs.base import (
    SHAPES,
    EncoderConfig,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    RunConfig,
    RWKVConfig,
    ShapeConfig,
    SSMConfig,
    YaRNConfig,
    applicable_shapes,
    input_specs,
)

from repro.configs.qwen2_5_32b import CONFIG as _qwen2_5_32b
from repro.configs.deepseek_67b import CONFIG as _deepseek_67b
from repro.configs.gemma2_2b import CONFIG as _gemma2_2b
from repro.configs.deepseek_7b import CONFIG as _deepseek_7b
from repro.configs.zamba2_2p7b import CONFIG as _zamba2_2p7b
from repro.configs.whisper_base import CONFIG as _whisper_base
from repro.configs.qwen2_vl_2b import CONFIG as _qwen2_vl_2b
from repro.configs.rwkv6_1p6b import CONFIG as _rwkv6_1p6b
from repro.configs.deepseek_v2_lite import CONFIG as _deepseek_v2_lite
from repro.configs.arctic_480b import CONFIG as _arctic_480b

REGISTRY: dict[str, ModelConfig] = {
    c.name: c
    for c in [
        _qwen2_5_32b,
        _deepseek_67b,
        _gemma2_2b,
        _deepseek_7b,
        _zamba2_2p7b,
        _whisper_base,
        _qwen2_vl_2b,
        _rwkv6_1p6b,
        _deepseek_v2_lite,
        _arctic_480b,
    ]
}

ARCH_IDS = list(REGISTRY)


def get_config(name: str) -> ModelConfig:
    key = name.replace("_", "-")
    if key in REGISTRY:
        return REGISTRY[key]
    # allow prefix match (e.g. "deepseek-v2-lite" for "deepseek-v2-lite-16b")
    hits = [k for k in REGISTRY if k.startswith(key)]
    if len(hits) == 1:
        return REGISTRY[hits[0]]
    raise KeyError(f"unknown arch {name!r}; available: {ARCH_IDS}")


__all__ = [
    "ARCH_IDS", "REGISTRY", "get_config", "input_specs", "applicable_shapes",
    "SHAPES", "ShapeConfig", "ModelConfig", "RunConfig", "MLAConfig",
    "MoEConfig", "SSMConfig", "RWKVConfig", "EncoderConfig", "YaRNConfig",
]
