"""Mamba-2 SSD chunk scan (Pallas TPU).

One grid step processes one (batch*head, chunk) cell: the quadratic
intra-chunk term plus the contribution of the running inter-chunk state,
which is carried ACROSS grid steps in a VMEM scratch (TPU grids execute
minor-axis-sequentially, so the chunk axis acts as the recurrence loop —
the same producer->consumer overlap structure as the paper's bank
time-steps).

Layouts: x [BH, S, P]; dt [BH, S, 1]; A [BH, 1, 1] (per-head scalar,
pre-gathered); Bm/Cm [BH, S, N] (group-expanded via index maps upstream).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
            chunk: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # [L, P]
    dt = dt_ref[0].astype(jnp.float32)        # [L, 1]
    a = a_ref[0].astype(jnp.float32)          # [1, 1] (negative)
    bm = b_ref[0].astype(jnp.float32)         # [L, N]
    cm = c_ref[0].astype(jnp.float32)         # [L, N]

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    # Mosaic has no cumsum: the inclusive prefix sum is a lower-triangular
    # matmul, taken over [L, L] copies so that every operand is tile-shaped.
    # cum_sq[i, j] = cum_i and its transpose cum_sq.T[i, j] = cum_j.
    da = dt * a                               # [L, 1]
    da_sq = jnp.broadcast_to(da, (chunk, chunk))
    cum_sq = jnp.dot(causal.astype(jnp.float32), da_sq,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    cum = cum_sq[:, :1]                       # [L, 1]
    total = jnp.sum(da, axis=0, keepdims=True)    # [1, 1] = cum_{L-1}
    # intra-chunk: M[i,j] = (C_i . B_j) exp(cum_i - cum_j) dt_j, i >= j
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    decay = jnp.where(causal, jnp.exp(cum_sq - cum_sq.T), 0.0)
    dt_cols = jnp.broadcast_to(dt, (chunk, chunk)).T  # [i, j] = dt_j
    m = scores * decay * dt_cols
    y = jnp.dot(m, x, preferred_element_type=jnp.float32)

    # inter-chunk: y += (C exp(cum)) @ state_prev ; state update
    state = state_ref[...]                    # [N, P]
    y += jnp.dot(cm * jnp.exp(cum), state,
                 preferred_element_type=jnp.float32)
    wb = bm * (jnp.exp(total - cum) * dt)     # [L, N]
    sc = jnp.dot(wb.T, x, preferred_element_type=jnp.float32)
    state_ref[...] = state * jnp.exp(total) + sc
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan(x, dt, a, bm, cm, *, chunk: int = 128,
             interpret: bool = False):
    """x [BH, S, P]; dt [BH, S, 1]; a [BH, 1, 1]; bm/cm [BH, S, N]."""
    bh, s, p = x.shape
    n = bm.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    grid = (bh, s // chunk)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, bm, cm)
