"""deepseek-v2-lite [moe] — 27L d_model=2048 16H, latent attention (no q
LoRA, kv_lora_rank=512, qk 128 + 64 rope, v 128, YaRN factor 40 over 4096),
first layer dense (d_ff=10944), then 64 routed experts of 1408 (softmax
greedy top-6, gates not renormalised) + 2 shared, sequence-level balance
loss (alpha 0.001); vocab=102400, untied.
[hf deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]

Every routed expert is held here (``held_experts`` (0, 64)); a chip of an
expert-parallel deployment sets the range it holds."""
from repro.models.transformer import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite", family="moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=192,
        d_ff=10944, vocab=102400, rope_theta=1e4,
        kv_lora_rank=512, qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling={"factor": 40, "original_max_position_embeddings": 4096,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                      "mscale_all_dim": 0.707},
        first_k_dense=1, n_experts=64, top_k=6, n_shared_experts=2,
        moe_d_ff=1408, held_experts=(0, 64),
        aux_loss_alpha=0.001,
        citation="arXiv:2405.04434")
