"""DeepSeek-V2 — multi-head latent attention + DeepSeekMoE with 160
routed experts in 8 groups, top-6, 2 shared
[hf:deepseek-ai/DeepSeek-V2 config.json; arXiv:2405.04434 Sec. 2.1-2.2].

60L d_model=5120, 128H; MLA q_lora_rank=1536, kv_lora_rank=512,
qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128; layer 0 is
dense (first_k_dense_replace=1, intermediate_size=12288), the rest MoE
(moe_intermediate_size=1536, n_group=8, topk_group=3); vocab=102400,
context 163840 (YaRN rope scaling, elementwise).
"""
from repro.models.common import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        arch_id="deepseek_v2", family="moe",
        n_layers=60, d_model=5120, vocab=102400,
        n_heads=128, n_kv_heads=128, d_ff=1536,
        n_experts=160, top_k=6, n_shared_experts=2,
        n_expert_groups=8, topk_groups=3,
        n_dense_layers=1, d_ff_dense=12288,
        q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        max_seq=163840,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        arch_id="deepseek_v2_smoke", family="moe",
        n_layers=2, d_model=64, vocab=256,
        n_heads=4, n_kv_heads=4, d_ff=32,
        n_experts=8, top_k=2, n_shared_experts=1,
        n_expert_groups=2, topk_groups=1,
        n_dense_layers=1, d_ff_dense=96,
        q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
    )
