"""Family dispatch: one API across the zoo's architectures.

``audio`` (encoder-decoder) dispatches to ``encdec``; everything else to
``lm``. All functions are pure and jit-friendly. The substrate builds
GQA/MQA attention and the fixed block of each family only: a config
with multi-head latent attention (``kv_lora_rank > 0``, DeepSeek-V2) or
with a layer pattern of one mixer per layer (Nemotron-H's Mamba-2, MoE
and attention layers) is refused rather than built as something else
under its name; such a config is served by ``repro.workloads`` alone.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp

from . import encdec, lm
from .common import ModelConfig

PyTree = Any


def _check_buildable(cfg: ModelConfig) -> None:
    """Raise ``NotImplementedError`` for a config the substrate cannot
    build faithfully (latent attention, a layer pattern). The
    constructors of parameters and caches call it, so nothing downstream
    runs on such a config."""
    if cfg.kv_lora_rank:
        raise NotImplementedError(
            f"{cfg.arch_id}: the LM substrate has no multi-head latent "
            "attention (kv_lora_rank > 0); lower it with repro.workloads")
    if cfg.layer_pattern:
        raise NotImplementedError(
            f"{cfg.arch_id}: the LM substrate has no layer pattern "
            f"({cfg.layer_pattern!r}: Mamba-2, MoE without attention, "
            "attention without MLP); lower it with repro.workloads")


def init_params(cfg: ModelConfig, key) -> PyTree:
    _check_buildable(cfg)
    if cfg.family == "audio":
        return encdec.init_params(cfg, key)
    return lm.init_params(cfg, key)


def param_shapes(cfg: ModelConfig) -> PyTree:
    _check_buildable(cfg)
    if cfg.family == "audio":
        return encdec.param_shapes(cfg)
    return lm.param_shapes(cfg)


def loss_fn(cfg: ModelConfig, params: PyTree, batch: Dict):
    if cfg.family == "audio":
        return encdec.loss_fn(cfg, params, batch)
    return lm.loss_fn(cfg, params, batch)


def forward(cfg: ModelConfig, params: PyTree, batch: Dict):
    if cfg.family == "audio":
        return encdec.forward(cfg, params, batch["tokens"],
                              batch["frames"])
    return lm.forward(cfg, params, batch["tokens"],
                      batch.get("extra_embeds"))


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> PyTree:
    _check_buildable(cfg)
    if cfg.family == "audio":
        return encdec.init_cache(cfg, batch, max_seq, cfg.enc_frames)
    return lm.init_cache(cfg, batch, max_seq)


def decode_step(cfg: ModelConfig, params: PyTree, cache: PyTree, tokens):
    if cfg.family == "audio":
        return encdec.decode_step(cfg, params, cache, tokens)
    return lm.decode_step(cfg, params, cache, tokens)


def prefill(cfg: ModelConfig, params: PyTree, tokens, max_seq: int,
            frames=None):
    if cfg.family == "audio":
        cache = encdec.init_cache(cfg, tokens.shape[0], max_seq,
                                  cfg.enc_frames)
        cache = encdec.prime_cross_cache(cfg, params, cache, frames)
        # teacher-force the prompt through decode steps is wasteful; run
        # forward once and only keep the cache of self-attn prefill
        logits, _ = encdec.forward(cfg, params, tokens, frames)
        return logits[:, -1, :], cache
    return lm.prefill(cfg, params, tokens, max_seq)
