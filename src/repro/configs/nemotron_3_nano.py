"""NVIDIA Nemotron-3-Nano-30B-A3B — a Nemotron-H hybrid: each layer is
one mixer, Mamba-2, MoE or GQA attention, as ``hybrid_override_pattern``
says [hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json].

52L d_model=2688, vocab=131072; pattern ``MEMEM*EMEMEM*E...MEMEMEME``
(23 Mamba-2, 23 MoE, 6 attention). Mamba-2: 64 heads of dim 64 (inner
width 4096, not expand x d_model = 5376), ssm_state_size=128,
n_groups=8, chunk_size=128, conv_kernel=4. MoE: 128 routed experts,
top-6, sigmoid routing with normalised gates x routed_scaling_factor
2.5; non-gated relu^2 experts of width 1856 and one shared expert of
width 3712. Attention: 32 query heads, 2 KV heads, head_dim=128, no
MLP. Context 262144.
"""
from repro.models.common import ModelConfig

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def config() -> ModelConfig:
    return ModelConfig(
        arch_id="nemotron_3_nano", family="hybrid",
        n_layers=52, d_model=2688, vocab=131072,
        n_heads=32, n_kv_heads=2, head_dim=128, d_ff=1856,
        mlp="relu2", n_experts=128, top_k=6,
        n_shared_experts=1, d_ff_shared=3712,
        ssm_state=128, ssm_n_heads=64, ssm_head_dim=64, ssm_groups=8,
        ssm_conv=4, ssm_chunk=128, layer_pattern=PATTERN,
        max_seq=262144,
    )


def smoke_config() -> ModelConfig:
    """One pattern period at toy widths, every structural feature kept:
    Mamba heads x head dim (96) != expand x d_model (128), shared width
    != routed width, GQA group 2, head_dim != d_model / n_heads. Expert
    capacity ``n_experts / top_k`` gives every expert a slot per token,
    dropless as the published routing is, at every length."""
    return ModelConfig(
        arch_id="nemotron_3_nano_smoke", family="hybrid",
        n_layers=7, d_model=64, vocab=256,
        n_heads=4, n_kv_heads=2, head_dim=24, d_ff=32,
        mlp="relu2", n_experts=8, top_k=2, capacity_factor=4.0,
        n_shared_experts=1, d_ff_shared=48,
        ssm_state=16, ssm_n_heads=6, ssm_head_dim=16, ssm_groups=2,
        ssm_conv=4, ssm_chunk=4, layer_pattern=PATTERN[:7],
    )
