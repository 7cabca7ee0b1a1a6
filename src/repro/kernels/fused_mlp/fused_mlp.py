"""Overlap-fused SwiGLU MLP kernel (Pallas TPU).

This is the paper's computational-overlap idea mapped onto the TPU memory
hierarchy (DESIGN.md Section 3, level 1): the consumer matmul (@W2)
consumes each d_ff block of the producer (x@W1, x@W3) AS SOON as it is
produced, in VMEM — the [M, d_ff] intermediate never round-trips to HBM:

    y = sum_j act(x @ W1[:, j]) * (x @ W3[:, j]) @ W2[j, :]

Grid (M_tiles, F_tiles), F minor: the fp32 accumulator for one M tile
lives in a VMEM scratch across the F sweep (the PIM "bank time step" maps
to one (m, j) grid step; "ready-time" = the producer block's grid step,
which immediately precedes its consumption).

HBM traffic: x read F_tiles times, W1/W3/W2 read once, y written once —
vs the unfused 2x(d_ff intermediate) + weights (traffic model in
``ops.hbm_bytes_fused``).

VMEM: every block is double-buffered, plus the fp32 accumulator. At
granite_8b's width (K=4096, bf16) with tm=256, tf=512 that is 36 MiB, over
the compiler's default scoped limit (16 MiB on v5e), so the kernel asks
for what its tiles need (``vmem_bytes``); v5e has 128 MiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_ref, *,
            n_ftiles: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    # producer block: h_j = silu(x @ W1_j) * (x @ W3_j)   (in VMEM)
    h1 = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
    h3 = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
    h = (h1 * jax.lax.logistic(h1)) * h3
    # consumer: overlapped accumulation into the output tile
    acc_ref[...] += jnp.dot(h.astype(x.dtype), w2_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_ftiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def vmem_bytes(tm: int, tf: int, k: int, itemsize: int) -> int:
    """VMEM the kernel's blocks take: x, W1, W3, W2 and y tiles, each
    double-buffered, plus the fp32 accumulator."""
    blocks = (tm * k + 3 * k * tf + tm * k) * itemsize
    return 2 * blocks + tm * k * 4


def fused_mlp(x, w1, w3, w2, *, tm: int = 128, tf: int = 512,
              interpret: bool = False):
    """x [M, K]; w1/w3 [K, F]; w2 [F, K] -> [M, K]."""
    m, k = x.shape
    f = w1.shape[1]
    tm = min(tm, m)
    tf = min(tf, f)
    assert m % tm == 0 and f % tf == 0, (m, tm, f, tf)
    grid = (m // tm, f // tf)
    # headroom for Mosaic's own internal scratch
    limit = vmem_bytes(tm, tf, k, x.dtype.itemsize) + (4 << 20)
    return pl.pallas_call(
        functools.partial(_kernel, n_ftiles=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, tf), lambda i, j: (0, j)),
            pl.BlockSpec((k, tf), lambda i, j: (0, j)),
            pl.BlockSpec((tf, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tm, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, k), x.dtype),
        scratch_shapes=[pltpu.VMEM((tm, k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
    )(x, w1, w3, w2)
