"""Batch construction: random token batches for tests and training."""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from .common import ModelConfig


def make_train_batch(cfg: ModelConfig, batch: int, seq: int,
                     seed: int = 0) -> Dict:
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, cfg.vocab, size=(batch, seq + 1)).astype(np.int32)
    out = {"tokens": jnp.asarray(toks[:, :-1]),
           "labels": jnp.asarray(toks[:, 1:])}
    if cfg.family == "audio":
        out["frames"] = jnp.asarray(
            rng.randn(batch, cfg.enc_frames, cfg.d_model),
            dtype=cfg.compute_dtype)
    return out


def make_decode_tokens(cfg: ModelConfig, batch: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, cfg.vocab, size=(batch,)),
                       dtype=jnp.int32)
