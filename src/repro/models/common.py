"""Shared model substrate: configs, norms, RoPE, initializers.

All models store per-layer parameters STACKED on a leading layer axis and
apply blocks with ``jax.lax.scan`` — HLO stays compact (fast multi-pod
lowering, parseable collective schedule) and layer count is a free config
knob.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any

VOCAB_PAD = 512  # pad vocab so the unembed shards on any model axis <= 512


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str                  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_ff: int = 0
    head_dim: int = 0
    norm: str = "rmsnorm"        # rmsnorm | layernorm_np (OLMo)
    mlp: str = "swiglu"          # swiglu | gelu | relu2 (non-gated relu^2)
    rope_theta: float = 10_000.0
    use_rope: bool = True
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    # width of each shared expert where it differs from the routed
    # experts' ``d_ff`` (Nemotron-H ``moe_shared_expert_intermediate_size``)
    d_ff_shared: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # routing/capacity is computed per data shard (set to the mesh's data
    # extent by the launcher; 1 on single-device tests). Without this the
    # global-view [T, E, cap] dispatch tensors scale with GLOBAL tokens
    # (observed 162 GiB/device on deepseek_moe_16b train_4k).
    moe_shards: int = 1
    # "gather": sort/gather dispatch, ~0 dispatch FLOPs (production);
    # "einsum": GShard one-hot einsum dispatch (reference + ablation —
    # costs ~2x the expert FLOPs at deepseek's top-6/64 shapes).
    moe_impl: str = "gather"
    # mesh axes the token-shard dim maps to; when set, the combine path
    # re-shards expert outputs back to data-parallel BEFORE the gather
    # (explicit all-to-all) — otherwise XLA lowers the cross-expert-shard
    # gather as a masked all-reduce and can defer the MoE psum all the
    # way to the fp32 logits (observed 3.4 GB/step all-reduce).
    moe_data_axes: tuple = ()
    # mesh axis the expert dim is sharded on; when set, the dispatched
    # activations are pinned to (data, expert) sharding so the data->
    # expert reshard is one all-to-all instead of an all-gather of the
    # full [E, cap, D] slot tensor.
    moe_expert_axis: str = ""
    # routed experts fall into this many groups (DeepSeek-V2 ``n_group``,
    # device-limited routing); a token's experts come from ``topk_groups``
    # of them
    n_expert_groups: int = 0
    topk_groups: int = 0
    # leading dense blocks before the MoE blocks (DeepSeek
    # ``first_k_dense_replace``) and their MLP width
    n_dense_layers: int = 0
    d_ff_dense: int = 0
    # multi-head latent attention (MLA, arXiv:2405.04434 Sec. 2.1): the
    # query and key/value low ranks and the per-head dims of the rope and
    # nope query/key parts and of the value; kv_lora_rank > 0 selects MLA
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # SSM (Mamba-2 / SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # Mamba-2 head count where the modelling code fixes it, so that
    # ``d_inner = ssm_n_heads * ssm_head_dim`` rather than
    # ``ssm_expand * d_model`` (Nemotron-H ``mamba_num_heads``)
    ssm_n_heads: int = 0
    # hybrid (Zamba-2): one shared attention block applied every k layers
    attn_every: int = 0
    # one mixer per layer, in order (Nemotron-H ``hybrid_override_pattern``):
    # "M" Mamba-2, "E" MoE without attention, "*" attention without MLP;
    # empty means the family's fixed block
    layer_pattern: str = ""
    # encoder-decoder (Whisper backbone)
    enc_layers: int = 0
    enc_frames: int = 1500
    # vlm (LLaVA-NeXT backbone): anyres patch embeddings prepended (stub)
    img_tokens: int = 0
    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # decoder learned-position table size (encoder-decoder family)
    max_seq: int = 32768
    # remat: "full" recomputes everything in backward (min memory);
    # "dots" saves matmul outputs (no recompute of MXU work — right when
    # HBM headroom exists, see EXPERIMENTS.md Perf olmo iteration 2)
    remat_policy: str = "full"

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def padded_vocab(self) -> int:
        return ((self.vocab + VOCAB_PAD - 1) // VOCAB_PAD) * VOCAB_PAD

    @property
    def d_inner(self) -> int:
        if self.ssm_n_heads:
            return self.ssm_n_heads * self.ssm_head_dim
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def is_ssm_family(self) -> bool:
        return self.family in ("ssm", "hybrid")

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def params_count(self, params: PyTree) -> int:
        return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm_np(x, _scale_unused=None, eps=1e-5):
    """Non-parametric LayerNorm (OLMo: no scale/bias)."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def apply_norm(cfg: ModelConfig, x, scale):
    if cfg.norm == "layernorm_np":
        return layernorm_np(x)
    return rmsnorm(x, scale)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(cfg: ModelConfig, positions):
    """positions [*] -> (cos, sin) each [*, hd/2], float32."""
    hd = cfg.hd
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2,
                                               dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [..., S, H, hd]; cos/sin [S, hd/2] (broadcast over batch/heads)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in, d_out, dtype, scale: Optional[float] = None):
    std = scale if scale is not None else (1.0 / math.sqrt(d_in))
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * std).astype(dtype)


def split_keys(key, names):
    keys = jax.random.split(key, len(names))
    return dict(zip(names, keys))


def cast_tree(tree: PyTree, dtype) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype,
                                                    jnp.floating) else x,
        tree)
