"""Decoder-only language models (dense / moe / ssm / hybrid / vlm).

Per-layer parameters are stacked on a leading layer axis and applied with
``jax.lax.scan``. The hybrid (Zamba-2) family adds ONE shared attention
block (shared weights) applied every ``attn_every`` layers via
``lax.cond`` inside the scan.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (attention, decode_attention, init_attn,
                        init_kv_cache, prefill_into_cache)
from .common import (ModelConfig, apply_norm, cast_tree, dense_init,
                     split_keys)
from .mlp import init_mlp, init_moe, mlp, moe
from .ssm import init_mamba2, init_ssm_cache, mamba2_block, mamba2_decode

PyTree = Any


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, key, dtype) -> Dict:
    ks = split_keys(key, ["attn", "ffn"])
    p: Dict = {}
    if cfg.family in ("dense", "moe", "vlm"):
        p["attn_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["attn"] = init_attn(cfg, ks["attn"], dtype=dtype)
        p["ffn_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        if cfg.family == "moe":
            p["moe"] = init_moe(cfg, ks["ffn"], dtype=dtype)
        else:
            p["mlp"] = init_mlp(cfg, ks["ffn"], dtype=dtype)
    elif cfg.family in ("ssm", "hybrid"):
        p["ssm_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["ssm"] = init_mamba2(cfg, ks["attn"], dtype=dtype)
    else:
        raise ValueError(cfg.family)
    return p


def init_params(cfg: ModelConfig, key) -> PyTree:
    dtype = jnp.dtype(cfg.param_dtype)
    ks = split_keys(key, ["embed", "unembed", "layers", "shared"])
    layer_keys = jax.random.split(ks["layers"], cfg.n_layers)
    layers = jax.vmap(lambda k: _init_layer(cfg, k, dtype))(layer_keys)
    params = {
        "embed": dense_init(ks["embed"], cfg.padded_vocab, cfg.d_model,
                            dtype, scale=1.0),
        "unembed": dense_init(ks["unembed"], cfg.d_model,
                              cfg.padded_vocab, dtype),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "layers": layers,
    }
    if cfg.family == "hybrid" and cfg.attn_every:
        sk = split_keys(ks["shared"], ["attn", "mlp"])
        params["shared_attn"] = {
            "norm": jnp.ones((cfg.d_model,), jnp.float32),
            "attn": init_attn(cfg, sk["attn"], dtype=dtype),
            "mlp_norm": jnp.ones((cfg.d_model,), jnp.float32),
            "mlp": init_mlp(cfg, sk["mlp"], dtype=dtype),
        }
    return params


def param_shapes(cfg: ModelConfig) -> PyTree:
    """ShapeDtypeStruct tree without allocating (compile-test input)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# Forward (training / scoring)
# ---------------------------------------------------------------------------

def _vocab_mask(cfg: ModelConfig, logits):
    if cfg.padded_vocab == cfg.vocab:
        return logits
    mask = (jnp.arange(cfg.padded_vocab) < cfg.vocab)
    return jnp.where(mask[None, None, :], logits, -1e9)


def _shared_attn_apply(cfg, shared, x):
    h = apply_norm(cfg, x, shared["norm"])
    x = x + attention(cfg, shared["attn"], h, causal=True)
    h = apply_norm(cfg, x, shared["mlp_norm"])
    return x + mlp(cfg, shared["mlp"], h)


def forward(cfg: ModelConfig, params: PyTree, tokens,
            extra_embeds=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B,S] -> (logits [B,S,Vp], aux_loss scalar).

    ``extra_embeds`` [B,S_img,D] (vlm/audio stub frontends) is prepended;
    its positions are dropped from the returned logits."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"].astype(cdt)[tokens]
    n_extra = 0
    if extra_embeds is not None:
        n_extra = extra_embeds.shape[1]
        x = jnp.concatenate([extra_embeds.astype(cdt), x], axis=1)

    shared = params.get("shared_attn")

    def body(carry, inp):
        x, aux = carry
        idx, lp = inp
        if cfg.family in ("ssm", "hybrid"):
            h = apply_norm(cfg, x, lp["ssm_norm"])
            x = x + mamba2_block(cfg, lp["ssm"], h)
            if shared is not None and cfg.attn_every:
                x = jax.lax.cond(
                    (idx % cfg.attn_every) == cfg.attn_every - 1,
                    lambda v: _shared_attn_apply(cfg, shared, v),
                    lambda v: v, x)
        else:
            h = apply_norm(cfg, x, lp["attn_norm"])
            x = x + attention(cfg, lp["attn"], h, causal=True)
            h = apply_norm(cfg, x, lp["ffn_norm"])
            if cfg.family == "moe":
                y, a = moe(cfg, lp["moe"], h)
                x = x + y
                aux = aux + a
            else:
                x = x + mlp(cfg, lp["mlp"], h)
        return (x, aux), None

    # remat: back-prop recomputes inside each layer; only layer inputs are
    # saved — required to fit train_4k activations in HBM at 4k x 16/device.
    # "dots" policy additionally saves matmul outputs (skips recompute of
    # MXU work when HBM headroom exists).
    if cfg.remat_policy == "dots":
        ck = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif cfg.remat_policy == "mlp":
        ck = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "mlp_hidden"))
    else:
        ck = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(
        ck, (x, jnp.zeros((), jnp.float32)),
        (jnp.arange(cfg.n_layers), params["layers"]))
    x = apply_norm(cfg, x, params["final_norm"])
    logits = x @ params["unembed"].astype(cdt)
    logits = _vocab_mask(cfg, logits)
    if n_extra:
        logits = logits[:, n_extra:, :]
    return logits, aux


def loss_fn(cfg: ModelConfig, params: PyTree, batch: Dict) -> Tuple:
    """Next-token cross entropy. batch: tokens [B,S], labels [B,S]."""
    logits, aux = forward(cfg, params, batch["tokens"],
                          batch.get("extra_embeds"))
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                               axis=-1)[..., 0]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(gold)
    ce = ((logz - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# KV/SSM caches + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> PyTree:
    cdt = jnp.dtype(cfg.compute_dtype)
    L = cfg.n_layers
    if cfg.family in ("dense", "moe", "vlm"):
        kv = jax.vmap(lambda _: init_kv_cache(
            batch, max_seq, cfg.n_kv_heads, cfg.hd, cdt))(jnp.arange(L))
        return {"layers": kv, "pos": jnp.zeros((), jnp.int32)}
    if cfg.family == "ssm":
        ssm = jax.vmap(lambda _: init_ssm_cache(cfg, batch, cdt))(
            jnp.arange(L))
        return {"layers": ssm, "pos": jnp.zeros((), jnp.int32)}
    # hybrid: ssm cache per layer + shared-attn KV with ONE slot per
    # shared-block invocation (L/attn_every), not per layer — 6x less
    # cache at zamba2's attn_every=6
    ssm = jax.vmap(lambda _: init_ssm_cache(cfg, batch, cdt))(
        jnp.arange(L))
    n_slots = max(1, (L + cfg.attn_every - 1) // cfg.attn_every)
    kv = jax.vmap(lambda _: init_kv_cache(
        batch, max_seq, cfg.n_kv_heads, cfg.hd, cdt))(jnp.arange(n_slots))
    return {"layers": ssm, "attn": kv,
            "pos": jnp.zeros((), jnp.int32)}


def decode_step(cfg: ModelConfig, params: PyTree, cache: PyTree,
                tokens) -> Tuple[jnp.ndarray, PyTree]:
    """tokens [B] -> (logits [B,Vp], new cache). One token for the whole
    batch (the serving engine batches requests at this granularity)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    pos = cache["pos"]
    x = params["embed"].astype(cdt)[tokens][:, None, :]
    shared = params.get("shared_attn")

    def _slot_get(kv_all, slot):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, slot, 0,
                                                   keepdims=False),
            kv_all)

    def _slot_set(kv_all, slot, kv_one):
        return jax.tree_util.tree_map(
            lambda a, b: jax.lax.dynamic_update_index_in_dim(
                a, b.astype(a.dtype), slot, 0), kv_all, kv_one)

    def body(carry, inp):
        x, kv_all = carry
        idx, lp, lc = inp
        if cfg.family in ("ssm", "hybrid"):
            h = apply_norm(cfg, x, lp["ssm_norm"])
            y, sc = mamba2_decode(cfg, lp["ssm"], h, lc)
            x = x + y
            if cfg.family == "hybrid" and shared is not None:
                def do_attn(args):
                    v, kva = args
                    slot = idx // cfg.attn_every
                    ac = _slot_get(kva, slot)
                    hh = apply_norm(cfg, v, shared["norm"])
                    yy, ac = decode_attention(cfg, shared["attn"], hh,
                                              ac, pos)
                    v = v + yy
                    hh = apply_norm(cfg, v, shared["mlp_norm"])
                    return (v + mlp(cfg, shared["mlp"], hh),
                            _slot_set(kva, slot, ac))
                x, kv_all = jax.lax.cond(
                    (idx % cfg.attn_every) == cfg.attn_every - 1,
                    do_attn, lambda a: a, (x, kv_all))
            return (x, kv_all), sc
        h = apply_norm(cfg, x, lp["attn_norm"])
        y, lc = decode_attention(cfg, lp["attn"], h, lc, pos)
        x = x + y
        h = apply_norm(cfg, x, lp["ffn_norm"])
        if cfg.family == "moe":
            y, _ = moe(cfg, lp["moe"], h)
            x = x + y
        else:
            x = x + mlp(cfg, lp["mlp"], h)
        return (x, kv_all), lc

    kv0 = cache.get("attn", jnp.zeros((), cdt))
    (x, kv_new), new_layers = jax.lax.scan(
        body, (x, kv0),
        (jnp.arange(cfg.n_layers), params["layers"], cache["layers"]))
    x = apply_norm(cfg, x, params["final_norm"])
    logits = x[:, 0, :] @ params["unembed"].astype(cdt)
    logits = _vocab_mask(cfg, logits[:, None, :])[:, 0, :]
    out = {"layers": new_layers, "pos": pos + 1}
    if "attn" in cache:
        out["attn"] = kv_new
    return logits, out


def prefill(cfg: ModelConfig, params: PyTree, tokens,
            max_seq: int) -> Tuple[jnp.ndarray, PyTree]:
    """Prefill a prompt into a fresh cache; returns (last logits, cache)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    b, s = tokens.shape
    cache = init_cache(cfg, b, max_seq)
    x = params["embed"].astype(cdt)[tokens]
    shared = params.get("shared_attn")

    def _slot_set(kv_all, slot, kv_one):
        return jax.tree_util.tree_map(
            lambda a, v: jax.lax.dynamic_update_index_in_dim(
                a, v.astype(a.dtype), slot, 0), kv_all, kv_one)

    def body(carry, inp):
        x, kv_all = carry
        idx, lp, lc = inp
        if cfg.family in ("ssm", "hybrid"):
            h = apply_norm(cfg, x, lp["ssm_norm"])
            # run block and also refresh the decode cache (state + conv)
            y = mamba2_block(cfg, lp["ssm"], h)
            sc = _ssm_cache_from_prefill(cfg, lp["ssm"], h, lc)
            x = x + y
            if cfg.family == "hybrid" and shared is not None:
                def do_attn(args):
                    v, kva = args
                    slot = idx // cfg.attn_every
                    ac = jax.tree_util.tree_map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, slot, 0, keepdims=False), kva)
                    hn = apply_norm(cfg, v, shared["norm"])
                    yy, ac = prefill_into_cache(cfg, shared["attn"],
                                                hn, ac)
                    v = v + yy
                    hn = apply_norm(cfg, v, shared["mlp_norm"])
                    return (v + mlp(cfg, shared["mlp"], hn),
                            _slot_set(kva, slot, ac))
                x, kv_all = jax.lax.cond(
                    (idx % cfg.attn_every) == cfg.attn_every - 1,
                    do_attn, lambda a: a, (x, kv_all))
            return (x, kv_all), sc
        h = apply_norm(cfg, x, lp["attn_norm"])
        y, lc = prefill_into_cache(cfg, lp["attn"], h, lc)
        x = x + y
        h = apply_norm(cfg, x, lp["ffn_norm"])
        if cfg.family == "moe":
            y, _ = moe(cfg, lp["moe"], h)
            x = x + y
        else:
            x = x + mlp(cfg, lp["mlp"], h)
        return (x, kv_all), lc

    cdt0 = cache.get("attn", jnp.zeros((), cdt))
    (x, kv_new), new_layers = jax.lax.scan(
        body, (x, cdt0),
        (jnp.arange(cfg.n_layers), params["layers"], cache["layers"]))
    x = apply_norm(cfg, x, params["final_norm"])
    logits = x[:, -1, :] @ params["unembed"].astype(cdt)
    logits = _vocab_mask(cfg, logits[:, None, :])[:, 0, :]
    out = {"layers": new_layers, "pos": jnp.asarray(s, jnp.int32)}
    if "attn" in cache:
        out["attn"] = kv_new
    return logits, out


def _ssm_cache_from_prefill(cfg: ModelConfig, lp: Dict, h, sc) -> Dict:
    """Recompute the decode-time SSM cache from a prefilled sequence: final
    SSD state + last (conv_width - 1) pre-activation conv inputs."""
    import jax.nn as jnn
    b, s, _ = h.shape
    hh, p = cfg.ssm_heads, cfg.ssm_head_dim
    g, n = cfg.ssm_groups, cfg.ssm_state
    kw = cfg.ssm_conv
    xin = h @ lp["wx"].astype(h.dtype)
    Bv = h @ lp["wB"].astype(h.dtype)
    Cv = h @ lp["wC"].astype(h.dtype)
    dt = h @ lp["wdt"].astype(h.dtype)
    from .ssm import _causal_dw_conv, ssd_chunked
    xc = jnn.silu(_causal_dw_conv(xin, lp["conv_x"].astype(h.dtype)))
    Bc = jnn.silu(_causal_dw_conv(Bv, lp["conv_B"].astype(h.dtype)))
    Cc = jnn.silu(_causal_dw_conv(Cv, lp["conv_C"].astype(h.dtype)))
    dtp = jnn.softplus(dt.astype(jnp.float32) + lp["dt_bias"][None, None])
    A = -jnp.exp(lp["A_log"])
    _, final = ssd_chunked(xc.reshape(b, s, hh, p), dtp, A,
                           Bc.reshape(b, s, g, n), Cc.reshape(b, s, g, n),
                           chunk=min(cfg.ssm_chunk, s))
    def tail(v):
        return v[:, -(kw - 1):, :].astype(sc["conv_x"].dtype) \
            if s >= kw - 1 else jnp.pad(v, ((0, 0), (kw - 1 - s, 0),
                                            (0, 0))).astype(
                sc["conv_x"].dtype)
    return {"state": final, "conv_x": tail(xin), "conv_B": tail(Bv),
            "conv_C": tail(Cv)}
