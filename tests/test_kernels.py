"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret
mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

pytestmark = pytest.mark.kernels  # JAX/Pallas compile-heavy (see pytest.ini)

from repro.kernels.flash_attn import attention_ref, flash_attention_op
from repro.kernels.fused_mlp import fused_mlp_op, fused_mlp_ref
from repro.kernels.ssd_scan import ssd_ref, ssd_scan_op


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused MLP
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,f,tm,tf", [
    (128, 256, 512, 64, 128),
    (256, 128, 256, 128, 256),
    (64, 64, 128, 64, 64),
])
def test_fused_mlp_shapes(dtype, m, k, f, tm, tf):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = (jax.random.normal(ks[0], (m, k)) * 0.5).astype(dtype)
    w1 = (jax.random.normal(ks[1], (k, f)) * 0.05).astype(dtype)
    w3 = (jax.random.normal(ks[2], (k, f)) * 0.05).astype(dtype)
    w2 = (jax.random.normal(ks[3], (f, k)) * 0.05).astype(dtype)
    y = fused_mlp_op(x, w1, w3, w2, tm=tm, tf=tf, interpret=True)
    yr = fused_mlp_ref(x, w1, w3, w2)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))


@given(mi=st.integers(1, 4), ki=st.integers(1, 4), fi=st.integers(1, 4),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_fused_mlp_property(mi, ki, fi, seed):
    m, k, f = 64 * mi, 64 * ki, 64 * fi
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (m, k)) * 0.5
    w1 = jax.random.normal(ks[1], (k, f)) * 0.05
    w3 = jax.random.normal(ks[2], (k, f)) * 0.05
    w2 = jax.random.normal(ks[3], (f, k)) * 0.05
    y = fused_mlp_op(x, w1, w3, w2, tm=64, tf=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(fused_mlp_ref(x, w1, w3, w2)),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,h,kv,sq,sk,hd", [
    (2, 4, 2, 128, 128, 64),    # GQA g=2
    (1, 8, 1, 64, 256, 32),     # MQA, rectangular
    (2, 2, 2, 256, 256, 128),   # MHA
])
def test_flash_attention_shapes(dtype, causal, b, h, kv, sq, sk, hd):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b * h, sq, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b * kv, sk, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b * kv, sk, hd)).astype(dtype)
    y = flash_attention_op(q, k, v, causal=causal, tq=64, tk=64,
                           interpret=True)
    yr = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               **_tol(dtype))


@given(sq=st.sampled_from([64, 128, 192]),
       sk=st.sampled_from([64, 128, 256]),
       g=st.sampled_from([1, 2, 4]), seed=st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_flash_attention_property(sq, sk, g, seed):
    if sq > sk:  # causal with sq > sk is ill-posed in this layout
        sq = sk
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (2 * g, sq, 32))
    k = jax.random.normal(ks[1], (2, sk, 32))
    v = jax.random.normal(ks[2], (2, sk, 32))
    y = flash_attention_op(q, k, v, causal=True, tq=64, tk=64,
                           interpret=True)
    yr = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


def test_flash_matches_model_flash():
    """Kernel agrees with the model-layer einsum flash implementation."""
    from repro.models.attention import flash_attention as model_flash
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    b, s, h, kv, hd = 2, 128, 4, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, kv, hd))
    v = jax.random.normal(ks[2], (b, s, kv, hd))
    ym = model_flash(q, k, v, causal=True)
    # kernel layout: [B*KV*G, S, hd] with q grouped (b, kv, g)
    qk = q.reshape(b, s, kv, h // kv, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(b * h, s, hd)
    kk = k.transpose(0, 2, 1, 3).reshape(b * kv, s, hd)
    vk = v.transpose(0, 2, 1, 3).reshape(b * kv, s, hd)
    yk = flash_attention_op(qk, kk, vk, causal=True, tq=64, tk=64,
                            interpret=True)
    yk = yk.reshape(b, kv, h // kv, s, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(b, s, h, hd)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(ym),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,s,p,n,chunk", [
    (3, 64, 16, 8, 16),
    (2, 128, 32, 16, 32),
    (1, 64, 64, 128, 64),   # mamba2-780m head geometry
])
def test_ssd_scan_shapes(dtype, bh, s, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (bh, s, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bh, s, 1))).astype(
        dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (bh, 1, 1)) * 0.2).astype(
        dtype)
    bm = jax.random.normal(ks[3], (bh, s, n)).astype(dtype)
    cm = jax.random.normal(ks[4], (bh, s, n)).astype(dtype)
    y = ssd_scan_op(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    yr = ssd_ref(x, dt, a, bm, cm)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)


@given(chunks=st.sampled_from([1, 2, 4]), seed=st.integers(0, 2 ** 16))
@settings(max_examples=8, deadline=None)
def test_ssd_scan_property_chunk_invariance(chunks, seed):
    """Output must not depend on the chunk size (state handoff exact)."""
    bh, s, p, n = 2, 64, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (bh, s, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bh, s, 1)))
    a = -jnp.exp(jax.random.normal(ks[2], (bh, 1, 1)) * 0.2)
    bm = jax.random.normal(ks[3], (bh, s, n))
    cm = jax.random.normal(ks[4], (bh, s, n))
    y16 = ssd_scan_op(x, dt, a, bm, cm, chunk=16, interpret=True)
    y_var = ssd_scan_op(x, dt, a, bm, cm, chunk=16 * chunks,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y_var),
                               rtol=1e-4, atol=1e-4)


def test_ssd_scan_matches_model_ssd():
    """Kernel agrees with models.ssm.ssd_chunked (group expansion)."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, g, n = 2, 64, 4, 16, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.2)
    bm = jax.random.normal(ks[3], (b, s, g, n))
    cm = jax.random.normal(ks[4], (b, s, g, n))
    ym, _ = ssd_chunked(x, dt, a, bm, cm, chunk=16)
    # kernel layout [B*H, S, *] with group-expanded B/C
    xk = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtk = dt.transpose(0, 2, 1).reshape(b * h, s, 1)
    ak = jnp.broadcast_to(a[None, :], (b, h)).reshape(b * h, 1, 1)
    rep = h // g
    bk = jnp.repeat(bm, rep, axis=2).transpose(0, 2, 1, 3).reshape(
        b * h, s, n)
    ck = jnp.repeat(cm, rep, axis=2).transpose(0, 2, 1, 3).reshape(
        b * h, s, n)
    yk = ssd_scan_op(xk, dtk, ak, bk, ck, chunk=16, interpret=True)
    yk = yk.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(ym),
                               rtol=2e-4, atol=2e-4)
