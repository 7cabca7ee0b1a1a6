"""Batched serving engine: prefill + decode with a fixed-size KV cache.

Implements the inference side of the framework: a request batch is
prefilled through ``prefill`` (scored prompt, cache primed), then tokens
are emitted with the jitted single-token ``serve_step``. Greedy or
temperature sampling; per-sequence stop handling via an active mask
(continuous-batching-lite: finished slots keep decoding but their tokens
are masked out — slot recycling is the host loop's job).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import steps as steps_lib
from repro.launch.sharding import cache_specs, param_specs, to_shardings
from repro.models import model_zoo
from repro.models.common import ModelConfig

PyTree = Any


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 512
    max_new_tokens: int = 32
    temperature: float = 0.0       # 0 => greedy
    eos_id: Optional[int] = None
    seed: int = 0


class Engine:
    """Batched LM inference: jitted prefill + single-token decode loop
    with greedy or temperature sampling (module docstring)."""

    def __init__(self, cfg: ModelConfig, params: PyTree, mesh=None,
                 scfg: Optional[ServeConfig] = None):
        self.cfg = cfg
        self.scfg = scfg or ServeConfig()
        self.mesh = mesh
        self.params = params
        self._decode = jax.jit(steps_lib.make_decode_step(cfg))
        self._prefill = jax.jit(
            steps_lib.make_prefill_step(cfg, self.scfg.max_seq))

    def generate(self, prompts: np.ndarray,
                 frames: Optional[np.ndarray] = None) -> np.ndarray:
        """prompts [B, S_prompt] int32 -> [B, max_new_tokens]."""
        out = np.zeros((prompts.shape[0], self.scfg.max_new_tokens),
                       np.int32)
        for i, (tok, _) in enumerate(self._steps(prompts, frames)):
            out[:, i] = tok
        return out

    def generate_with_logits(self, prompts: np.ndarray,
                             frames: Optional[np.ndarray] = None):
        """Like ``generate``, and also returns the logits each emitted
        token was sampled from: [B, n_emitted, padded_vocab], in the
        compute dtype (n_emitted < max_new_tokens only on an early stop
        when every sequence hit ``eos_id``). Holds every step's logits on
        the device, O(B * n_emitted * padded_vocab): a checking path,
        not the serving one."""
        out = np.zeros((prompts.shape[0], self.scfg.max_new_tokens),
                       np.int32)
        steps = []
        for i, (tok, logits) in enumerate(self._steps(prompts, frames)):
            out[:, i] = tok
            steps.append(logits)
        return out, jnp.stack(steps, axis=1)

    def _steps(self, prompts: np.ndarray, frames: Optional[np.ndarray]):
        """Yields (emitted tokens [B] as numpy, the logits they were
        sampled from) once per emitted step; finished sequences emit
        ``eos_id``."""
        scfg = self.scfg
        batch = {"tokens": jnp.asarray(prompts)}
        if self.cfg.family == "audio":
            batch["frames"] = jnp.asarray(frames)
        logits, cache = self._prefill(self.params, batch)

        rng = jax.random.PRNGKey(scfg.seed)
        done = np.zeros((prompts.shape[0],), bool)
        tok = self._sample(logits, rng)
        for _ in range(scfg.max_new_tokens):
            yield np.where(done, scfg.eos_id or 0, np.asarray(tok)), logits
            if scfg.eos_id is not None:
                done |= np.asarray(tok) == scfg.eos_id
                if done.all():
                    break
            logits, cache = self._decode(self.params, cache, tok)
            rng, sub = jax.random.split(rng)
            tok = self._sample(logits, sub)

    def _sample(self, logits, rng):
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / self.scfg.temperature, axis=-1).astype(
                jnp.int32)
