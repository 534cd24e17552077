"""Architecture registry: the assigned configs + reduced smoke variants."""

from __future__ import annotations

from repro.configs.base import ModelConfig
from repro.configs.llama4_scout_17b_a16e import CONFIG as LLAMA4_SCOUT
from repro.configs.mixtral_8x7b import CONFIG as MIXTRAL_8X7B
from repro.configs.nemotron_4_15b import CONFIG as NEMOTRON_4_15B
from repro.configs.nemotron_h_47b import CONFIG as NEMOTRON_H_47B
from repro.configs.gemma3_12b import CONFIG as GEMMA3_12B
from repro.configs.qwen3_32b import CONFIG as QWEN3_32B
from repro.configs.qwen2_72b import CONFIG as QWEN2_72B
from repro.configs.rwkv6_7b import CONFIG as RWKV6_7B
from repro.configs.hubert_xlarge import CONFIG as HUBERT_XLARGE
from repro.configs.qwen2_vl_7b import CONFIG as QWEN2_VL_7B
from repro.configs.zamba2_2p7b import CONFIG as ZAMBA2_2P7B

ARCHS: dict[str, ModelConfig] = {
    c.name: c
    for c in [
        LLAMA4_SCOUT,
        MIXTRAL_8X7B,
        NEMOTRON_4_15B,
        GEMMA3_12B,
        QWEN3_32B,
        QWEN2_72B,
        RWKV6_7B,
        HUBERT_XLARGE,
        QWEN2_VL_7B,
        ZAMBA2_2P7B,
        NEMOTRON_H_47B,
    ]
}


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]


__all__ = ["ARCHS", "ModelConfig", "get_config"]
