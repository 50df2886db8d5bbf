"""deepseek-moe-16b [moe] — 28L d_model=2048 16H (kv=16) per-expert
d_ff=1408 vocab=102400; fine-grained MoE: 2 shared + 64 routed top-6.
[arXiv:2401.06066]

Deviation: the published model's first layer has a dense FF; here every
layer is MoE, on the capacity-dispatch path (deepseek-v2-lite has the
leading dense stack and the dropless held-expert layer)."""
from repro.models.transformer import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-moe-16b", family="moe",
        n_layers=28, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
        d_ff=1408, vocab=102400, rope_theta=1e4,
        n_experts=64, top_k=6, n_shared_experts=2,
        citation="arXiv:2401.06066")
