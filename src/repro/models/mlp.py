"""Dense FFN (SwiGLU / GELU) and MoE (GShard-style dense dispatch)."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig, dense_init, split_keys


def init_mlp(cfg: ModelConfig, key, d_model=None, d_ff=None,
             dtype=jnp.float32) -> Dict:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    if cfg.mlp == "swiglu":
        ks = split_keys(key, ["w1", "w3", "w2"])
        return {"w1": dense_init(ks["w1"], d, f, dtype),
                "w3": dense_init(ks["w3"], d, f, dtype),
                "w2": dense_init(ks["w2"], f, d, dtype)}
    ks = split_keys(key, ["w1", "w2"])
    return {"w1": dense_init(ks["w1"], d, f, dtype),
            "w2": dense_init(ks["w2"], f, d, dtype)}


def mlp(cfg: ModelConfig, params: Dict, x):
    from jax.ad_checkpoint import checkpoint_name
    if "w3" in params:
        h = jax.nn.silu(x @ params["w1"].astype(x.dtype)) * \
            (x @ params["w3"].astype(x.dtype))
    else:
        h = jax.nn.gelu(x @ params["w1"].astype(x.dtype))
    # named for the "mlp" remat policy only — an unconditional
    # checkpoint_name degrades the default full-remat scan (observed 7x
    # worse terms on olmo train; see EXPERIMENTS.md Perf C3)
    if cfg.remat_policy == "mlp":
        h = checkpoint_name(h, "mlp_hidden")
    return h @ params["w2"].astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE: top-k routing, capacity-bounded einsum dispatch (GShard formulation).
# Experts shard on the "model" mesh axis (expert parallelism); the dispatch
# einsums lower to all-to-all-free sharded matmuls on a sharded mesh.
# ---------------------------------------------------------------------------

def init_moe(cfg: ModelConfig, key, dtype=jnp.float32) -> Dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    ks = split_keys(key, ["router", "w1", "w3", "w2", "sh"])
    std = 1.0 / (d ** 0.5)
    p = {
        "router": dense_init(ks["router"], d, e, jnp.float32),
        "w1": (jax.random.normal(ks["w1"], (e, d, f), jnp.float32)
               * std).astype(dtype),
        "w3": (jax.random.normal(ks["w3"], (e, d, f), jnp.float32)
               * std).astype(dtype),
        "w2": (jax.random.normal(ks["w2"], (e, f, d), jnp.float32)
               / (f ** 0.5)).astype(dtype),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * cfg.d_ff
        p["shared"] = init_mlp(cfg, ks["sh"], d_model=d, d_ff=fs,
                               dtype=dtype)
    return p


def moe(cfg: ModelConfig, params: Dict, x) -> Tuple[jnp.ndarray,
                                                    jnp.ndarray]:
    """Dispatch by cfg.moe_impl: "gather" (production) or "einsum"."""
    if cfg.moe_impl == "gather":
        return moe_gather(cfg, params, x)
    return moe_einsum(cfg, params, x)


def _route(cfg: ModelConfig, params, xt):
    """Shared router: returns (probs, gate_vals, gate_idx, pos, keep, cap).

    Shard-local routing: tokens are viewed as [n_shards, T_local] (the
    leading axis aligns with the batch/data sharding), so position-in-
    expert cumsums stay device-local and capacity scales with LOCAL
    tokens."""
    ns, tl, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = (xt.astype(jnp.float32) @ params["router"])    # [ns, tl, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)           # [ns, tl, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    cap = int(tl * k / e * cfg.capacity_factor)
    cap = max(cap, min(k, tl))
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)   # [ns,tl,k,E]
    flat = onehot.reshape(ns, tl * k, e)
    pos = jnp.cumsum(flat, axis=1) - flat                   # [ns,tl*k,E]
    pos = (pos * flat).sum(-1).reshape(ns, tl, k)           # [ns,tl,k]
    keep = pos < cap
    return probs, gate_vals * keep, gate_idx, pos, keep, cap, onehot


def _aux_loss(cfg, probs, onehot):
    me = probs.mean(axis=(0, 1))
    ce = onehot.sum(axis=2).astype(jnp.float32).mean(axis=(0, 1))
    return (me * ce).sum() * cfg.n_experts * cfg.router_aux_coef


def _experts(cfg, params, xe, dtype):
    """xe [ns, E, cap, D] -> [ns, E, cap, D] through per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("secd,edf->secf", xe,
                               params["w1"].astype(dtype)))
    h = h * jnp.einsum("secd,edf->secf", xe, params["w3"].astype(dtype))
    return jnp.einsum("secf,efd->secd", h, params["w2"].astype(dtype))


def moe_gather(cfg: ModelConfig, params: Dict, x):
    """Sort/gather dispatch: tokens are copied into their expert slot by
    a gather (O(tokens) traffic, no dispatch FLOPs); results are gathered
    back per (token, choice) and gate-combined. The data->expert reshard
    happens in the expert einsum (all-to-all under SPMD)."""
    b, s_len, d = x.shape
    t = b * s_len
    ns = cfg.moe_shards if t % cfg.moe_shards == 0 else 1
    tl = t // ns
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(ns, tl, d)
    probs, gates, gate_idx, pos, keep, cap, onehot = _route(
        cfg, params, xt)

    # slot table [ns, E*cap] <- token index (tl = "dropped" sentinel)
    slot = jnp.full((ns, e * cap), tl, jnp.int32)
    flat_slot = gate_idx * cap + pos                        # [ns, tl, k]
    tok_ids = jnp.broadcast_to(jnp.arange(tl, dtype=jnp.int32)[None, :,
                                                              None],
                               (ns, tl, k))
    # dropped assignments write out-of-range -> mode="drop" discards them
    slot = slot.at[
        jnp.arange(ns, dtype=jnp.int32)[:, None, None],
        jnp.where(keep, flat_slot, e * cap)
    ].set(tok_ids, mode="drop")
    # guard: sentinel row appended so dropped tokens read zeros
    xt_pad = jnp.concatenate(
        [xt, jnp.zeros((ns, 1, d), xt.dtype)], axis=1)
    xe = jnp.take_along_axis(
        xt_pad, slot[:, :, None].astype(jnp.int32), axis=1)
    xe = xe.reshape(ns, e, cap, d)
    if cfg.moe_expert_axis and ns > 1:
        axes = tuple(cfg.moe_data_axes) or (None,)
        xe = jax.lax.with_sharding_constraint(
            xe, jax.sharding.PartitionSpec(
                axes if len(axes) > 1 else axes[0],
                cfg.moe_expert_axis, None, None))

    ye = _experts(cfg, params, xe, x.dtype)                 # [ns,E,cap,D]

    # combine: reshard expert outputs back to data-parallel (one
    # all-to-all), then a shard-LOCAL back-gather per (token, choice).
    # (A scatter-add-in-slot-space combine was tried — psum of y instead
    # of the yef reshard — but its transpose gathers from a model-sharded
    # source and cost +50% collective bytes; see EXPERIMENTS.md Perf.)
    yef = ye.reshape(ns, e * cap, d)
    if cfg.moe_data_axes and ns > 1:
        axes = tuple(cfg.moe_data_axes)
        spec = jax.sharding.PartitionSpec(
            axes if len(axes) > 1 else axes[0], None, None)
        yef = jax.lax.with_sharding_constraint(yef, spec)
    back = jnp.take_along_axis(
        yef, jnp.where(keep, flat_slot, 0).reshape(ns, tl * k)[:, :,
                                                               None],
        axis=1).reshape(ns, tl, k, d)
    y = (back.astype(jnp.float32)
         * gates.astype(jnp.float32)[..., None]).sum(axis=2)
    y = y.astype(x.dtype)

    if "shared" in params:
        y = y + mlp(cfg, params["shared"], xt)
    return y.reshape(b, s_len, d), _aux_loss(cfg, probs, onehot)


def moe_einsum(cfg: ModelConfig, params: Dict, x) -> Tuple[jnp.ndarray,
                                                           jnp.ndarray]:
    """GShard one-hot einsum dispatch (reference implementation)."""
    b, s_len, d = x.shape
    t = b * s_len
    e, k = cfg.n_experts, cfg.top_k
    ns = cfg.moe_shards if t % cfg.moe_shards == 0 else 1
    tl = t // ns
    xt = x.reshape(ns, tl, d)
    logits = (xt.astype(jnp.float32) @ params["router"])    # [ns, tl, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)           # [ns, tl, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = int(tl * k / e * cfg.capacity_factor)
    cap = max(cap, min(k, tl))
    # position of each (token, choice) within its expert queue (LOCAL)
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)   # [ns,tl,k,E]
    flat = onehot.reshape(ns, tl * k, e)
    pos = jnp.cumsum(flat, axis=1) - flat                   # [ns,tl*k,E]
    pos = (pos * flat).sum(-1).reshape(ns, tl, k)           # [ns,tl,k]
    keep = pos < cap
    gate_vals = gate_vals * keep

    # dispatch/combine tensors [ns, tl, E, cap]
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap,
                            dtype=x.dtype)                  # [ns,tl,k,cap]
    disp = jnp.einsum("stke,stkc->stec", onehot.astype(x.dtype), pos_oh)
    comb = jnp.einsum("stke,stkc,stk->stec",
                      onehot.astype(jnp.float32),
                      pos_oh.astype(jnp.float32),
                      gate_vals.astype(jnp.float32)).astype(x.dtype)

    xe = jnp.einsum("stec,std->secd", disp, xt)             # [ns,E,cap,D]
    h = jax.nn.silu(jnp.einsum("secd,edf->secf", xe,
                               params["w1"].astype(x.dtype)))
    h = h * jnp.einsum("secd,edf->secf", xe,
                       params["w3"].astype(x.dtype))
    ye = jnp.einsum("secf,efd->secd", h, params["w2"].astype(x.dtype))
    y = jnp.einsum("stec,secd->std", comb, ye)

    if "shared" in params:
        y = y + mlp(cfg, params["shared"], xt)

    # GShard load-balancing aux loss
    me = probs.mean(axis=(0, 1))                            # [E]
    ce = onehot.sum(axis=2).astype(jnp.float32).mean(axis=(0, 1))
    aux = (me * ce).sum() * e * cfg.router_aux_coef
    return y.reshape(b, s_len, d), aux
