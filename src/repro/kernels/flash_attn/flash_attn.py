"""FlashAttention (Pallas TPU), causal + GQA.

The einsum-based online-softmax attention round-trips the [Sq, chunk]
score tensors to HBM between the two dots, which makes it memory-bound
at long context. This kernel keeps scores, running max and
normalizer in VMEM across the KV sweep (grid minor axis), writing only
the [Sq, hd] output — the paper's producer->consumer overlap applied to
the QK^T -> softmax -> AV chain.

Layouts: q [BH, Sq, hd]; k/v [BKV, Skv, hd]; GQA resolved in the k/v
BlockSpec index maps (no KV repetition in HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            tq: int, tk: int, n_ktiles: int, causal: bool, scale: float,
            q_offset: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: whole block above the diagonal -> skip all compute
    # (queries sit at the LAST sq positions of the kv sequence)
    run = True
    if causal:
        run = j * tk <= q_offset + (i + 1) * tq - 1

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale        # [tq, hd]
        k = k_ref[0].astype(jnp.float32)                # [tk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [tq, tk]
        if causal:
            rows = q_offset + i * tq + jax.lax.broadcasted_iota(
                jnp.int32, (tq, tk), 0)
            cols = j * tk + jax.lax.broadcasted_iota(
                jnp.int32, (tq, tk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]                             # [tq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)                  # [tq, 1]
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [tq, hd]
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(j == n_ktiles - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, tq: int = 256,
                    tk: int = 256, interpret: bool = False):
    """q [BH, Sq, hd]; k/v [BKV, Skv, hd]; BH = BKV * G. -> [BH, Sq, hd]"""
    bh, sq, hd = q.shape
    bkv, skv, _ = k.shape
    g = bh // bkv
    tq, tk = min(tq, sq), min(tk, skv)
    assert sq % tq == 0 and skv % tk == 0
    grid = (bh, sq // tq, skv // tk)
    scale = 1.0 / (hd ** 0.5)
    return pl.pallas_call(
        functools.partial(_kernel, tq=tq, tk=tk, n_ktiles=grid[2],
                          causal=causal, scale=scale,
                          q_offset=skv - sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tk, hd), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, tk, hd), lambda b, i, j: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, hd), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
