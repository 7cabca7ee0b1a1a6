"""One realistic shape per Pallas kernel, with its oracle and error bound.

Shared by ``chip_smoke.py`` (runs each compiled kernel on the chip against
its oracle), ``benchmarks/bench_kernels.py`` (times them) and
``tests/test_chip_compile.py`` (compiles them for a described v5e), so the
three always mean the same shapes.

Each bound is on ``max|kernel - oracle| / max|oracle|``, with the oracle
evaluated at ``highest`` matmul precision. bf16 keeps 8 significant bits
(relative rounding 2^-8 = 3.9e-3); the bound allows about five such
roundings of accumulation-order difference, and the SSD scan twice that
again for its 4096-step recurrence.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from .flash_attn import attention_ref, flash_attention_op
from .fused_mlp import fused_mlp_op, fused_mlp_ref
from .ssd_scan import ssd_ref, ssd_scan_op


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """A kernel at one shape: ``op(*inputs)`` against ``ref(*inputs)``."""

    name: str
    source: str                                  # where the shape is from
    args: Tuple[jax.ShapeDtypeStruct, ...]
    op: Callable
    ref: Callable
    make_inputs: Callable[[jax.Array], Tuple[jax.Array, ...]]
    rel_tol: float


def _normal(key, s: jax.ShapeDtypeStruct, scale: float = 1.0):
    return (jax.random.normal(key, s.shape, jnp.float32) * scale).astype(
        s.dtype)


def _fused_mlp_case() -> KernelCase:
    m, k, f = 2048, 4096, 14336
    args = (jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
            jax.ShapeDtypeStruct((k, f), jnp.bfloat16),
            jax.ShapeDtypeStruct((k, f), jnp.bfloat16),
            jax.ShapeDtypeStruct((f, k), jnp.bfloat16))

    def make(key):
        ks = jax.random.split(key, 4)
        return (_normal(ks[0], args[0]),
                _normal(ks[1], args[1], k ** -0.5),
                _normal(ks[2], args[2], k ** -0.5),
                _normal(ks[3], args[3], f ** -0.5))

    return KernelCase(
        "fused_mlp", "granite_8b SwiGLU MLP, 2048 tokens, one chip",
        args, functools.partial(fused_mlp_op, tm=256, tf=512),
        fused_mlp_ref, make, 2e-2)


def _flash_case() -> KernelCase:
    bh, s, hd = 16, 4096, 128
    args = tuple(jax.ShapeDtypeStruct((bh, s, hd), jnp.bfloat16)
                 for _ in range(3))

    def make(key):
        ks = jax.random.split(key, 3)
        return tuple(_normal(kk, a) for kk, a in zip(ks, args))

    return KernelCase(
        "flash_attention", "olmo_1b causal prefill, 16 heads x 4096 tokens",
        args, functools.partial(flash_attention_op, causal=True),
        functools.partial(attention_ref, causal=True), make, 2e-2)


def _ssd_case() -> KernelCase:
    bh, s, p, n = 48, 4096, 64, 128
    args = (jax.ShapeDtypeStruct((bh, s, p), jnp.bfloat16),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, s, n), jnp.bfloat16),
            jax.ShapeDtypeStruct((bh, s, n), jnp.bfloat16))

    def make(key):
        ks = jax.random.split(key, 5)
        return (_normal(ks[0], args[0]),
                jax.nn.softplus(_normal(ks[1], args[1]) - 2.0),
                -jnp.exp(_normal(ks[2], args[2], 0.2)),
                _normal(ks[3], args[3], n ** -0.5),
                _normal(ks[4], args[4], n ** -0.5))

    return KernelCase(
        "ssd_scan", "mamba2_780m SSD, 48 heads x 4096 tokens, chunk 256",
        args, functools.partial(ssd_scan_op, chunk=256), ssd_ref, make,
        5e-2)


def kernel_cases() -> Tuple[KernelCase, ...]:
    return (_fused_mlp_case(), _flash_case(), _ssd_case())


def check(case: KernelCase, seed: int = 0):
    """Run ``case`` on seeded inputs; returns (inputs, output, rel_err)."""
    inputs = case.make_inputs(jax.random.PRNGKey(seed))
    out = case.op(*inputs).block_until_ready()
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(case.ref)(*inputs)
    ref = ref.astype(jnp.float32)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref)) / jnp.max(
        jnp.abs(ref))
    return inputs, out, float(err)
