"""Assigned architecture registry: ``get(arch_id)`` -> ModelConfig."""
from repro.configs import (deepseek_moe_16b, deepseek_v2_lite,  # noqa: F401
                           llama_3_2_vision_90b, mixtral_8x7b,
                           phi4_mini_3_8b, qwen2_7b, qwen3_1_7b,
                           recurrentgemma_9b, rwkv6_7b, whisper_large_v3,
                           yi_9b)
from repro.configs import shapes  # noqa: F401

_REGISTRY = {
    "llama-3.2-vision-90b": llama_3_2_vision_90b.config,
    "yi-9b": yi_9b.config,
    "mixtral-8x7b": mixtral_8x7b.config,
    "whisper-large-v3": whisper_large_v3.config,
    "deepseek-moe-16b": deepseek_moe_16b.config,
    "deepseek-v2-lite": deepseek_v2_lite.config,
    "qwen3-1.7b": qwen3_1_7b.config,
    "recurrentgemma-9b": recurrentgemma_9b.config,
    "phi4-mini-3.8b": phi4_mini_3_8b.config,
    "qwen2-7b": qwen2_7b.config,
    "rwkv6-7b": rwkv6_7b.config,
}

ARCH_IDS = tuple(_REGISTRY)


def get(arch_id: str):
    if arch_id not in _REGISTRY:
        raise ValueError(f"unknown arch {arch_id!r}; have {ARCH_IDS}")
    return _REGISTRY[arch_id]()
