"""Sharding rules: DP (+pod) x TP/EP over the ("pod", "data", "model")
mesh, applied by parameter path.

Rules (Megatron-style):
  * embeddings shard d_model; unembed shards vocab (column-parallel with
    the loss's logsumexp all-reducing over "model");
  * attention q/k/v and MLP in-projections shard the OUT dim, o/w2 shard
    the IN dim (one all-reduce per block);
  * MoE experts shard the EXPERT axis ("model" = expert parallelism);
  * Mamba projections shard d_inner / heads / state groups;
  * anything not divisible by the model-axis size is replicated (e.g.
    whisper's 8 heads on a 16-way axis) — recorded, not fatal.

Batch dims shard over ("pod","data"). When the per-cell batch is smaller
than the data extent (long_500k: batch 1), KV/SSM caches shard the
SEQUENCE axis instead (sequence parallelism for the cache).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ModelConfig
from .mesh import batch_shard_size, data_axes, model_size

PyTree = Any

# param-name -> (axis index to shard with "model"), counted AFTER any
# stacked layer axis is skipped.
_OUT_DIM = {"wq", "wk", "wv", "w1", "w3", "wz", "wx", "wB", "wC", "wdt",
            "embed", "unembed", "enc_pos", "dec_pos"}
_IN_DIM = {"wo", "w2"}
_CONV = {"conv_x", "conv_B", "conv_C"}
_REPL = {"router", "dt_bias", "A_log", "D", "gn_scale"}


def _divisible(n: int, m: int) -> bool:
    return m > 0 and n % m == 0


def param_spec(path_keys, shape, msize: int) -> P:
    """PartitionSpec for one param leaf."""
    name = path_keys[-1]
    stacked = "layers" in path_keys or "encoder" in path_keys \
        or "decoder" in path_keys
    off = 1 if stacked else 0
    spec = [None] * len(shape)
    is_moe = any(k in ("moe",) for k in path_keys) and name in (
        "w1", "w2", "w3")
    if is_moe:
        if _divisible(shape[off], msize):
            spec[off] = "model"          # expert axis
    elif name in _OUT_DIM:
        ax = len(shape) - 1
        if _divisible(shape[ax], msize):
            spec[ax] = "model"
    elif name in _IN_DIM:
        ax = off
        if _divisible(shape[ax], msize):
            spec[ax] = "model"
    elif name in _CONV:
        ax = len(shape) - 1
        if _divisible(shape[ax], msize):
            spec[ax] = "model"
    # norms / scalars / _REPL stay replicated
    return P(*spec)


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


def param_specs(tree: PyTree, mesh: Mesh, plan: str = "tp") -> PyTree:
    """Parallelism plans:
      * "tp": megatron-style tensor parallel on the model axis (baseline);
      * "dp": pure data parallel — params replicated, the model axis acts
        as extra batch parallelism (right for <10B dense models where TP
        all-reduces dominate the step, see EXPERIMENTS.md Section Perf);
      * "ep": experts stay sharded on the model axis (EP), all dense
        params replicated (MoE counterpart of "dp").
    """
    msize = model_size(mesh)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = []
    # under "ep" embeddings stay vocab/d_model-sharded: a replicated
    # unembed makes XLA split the logits matmul and then all-reduce full
    # fp32 logits (3.4 GB/microbatch on deepseek). Under "dp" the batch
    # occupies the model axis, so embeddings must NOT also use it.
    keep_tp = {"embed", "unembed", "enc_pos", "dec_pos"}
    for path, leaf in leaves:
        keys = [_path_str(p) for p in path]
        if plan == "ep" and keys[-1] in keep_tp:
            specs.append(param_spec(keys, leaf.shape, msize))
        elif plan == "dp":
            specs.append(P(*([None] * len(leaf.shape))))
        elif plan == "ep":
            is_moe = "moe" in keys and keys[-1] in ("w1", "w2", "w3")
            specs.append(param_spec(keys, leaf.shape, msize) if is_moe
                         else P(*([None] * len(leaf.shape))))
        else:
            specs.append(param_spec(keys, leaf.shape, msize))
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(tree: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(tree, mesh))


def zero_extend(spec: P, shape, mesh: Mesh,
                axes: Tuple[str, ...] = ("data",)) -> P:
    """ZeRO-style extension: additionally shard the first free axis over
    ``axes`` when divisible (used for optimizer state always, and for
    params under FSDP)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    flat = [a for e in entries if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    for combo in (axes, ("data",)):
        if any(a in flat for a in combo):
            continue
        size = 1
        for a in combo:
            size *= mesh.shape[a]
        for i, (e, n) in enumerate(zip(entries, shape)):
            if e is None and _divisible(n, size) and n >= size:
                entries[i] = combo if len(combo) > 1 else combo[0]
                return P(*entries)
    return P(*entries)


def opt_specs(param_spec_tree: PyTree, shapes: PyTree, mesh: Mesh,
              axes: Tuple[str, ...] = ("data",)) -> PyTree:
    """Specs for one Adam moment tree (mirrors params + ZeRO sharding)."""
    return jax.tree_util.tree_map(
        lambda s, x: zero_extend(s, x.shape, mesh, axes),
        param_spec_tree, shapes,
        is_leaf=lambda x: isinstance(x, P))


def batch_specs(cfg: ModelConfig, batch: int, mesh: Mesh,
                kind: str) -> PyTree:
    dp = data_axes(mesh)
    bs = batch_shard_size(mesh)
    bspec = dp if _divisible(batch, bs) else None
    if kind in ("train", "prefill"):
        out = {"tokens": P(bspec, None), "labels": P(bspec, None)}
        if cfg.family == "audio":
            out["frames"] = P(bspec, None, None)
        if kind == "prefill":
            out.pop("labels")
        return out
    return P(bspec)  # decode tokens [B]


def cache_specs(cfg: ModelConfig, batch: int, mesh: Mesh,
                cache_tree: PyTree) -> PyTree:
    """Shard KV caches: batch over data axes when divisible, otherwise the
    sequence axis (long-context decode); kv-heads / ssm-heads over model
    when divisible."""
    dp = data_axes(mesh)
    bs = batch_shard_size(mesh)
    msize = model_size(mesh)
    batch_ok = _divisible(batch, bs)

    def spec_for(path, leaf) -> P:
        keys = [_path_str(p) for p in path]
        name = keys[-1]
        shp = leaf.shape
        if name == "pos":
            return P()
        if name in ("k", "v"):          # [L, B, S, kv, hd]
            kvs = "model" if _divisible(shp[3], msize) else None
            # kv heads narrower than the model axis (llava/granite kv=8 on
            # a 16-way axis): shard the SEQUENCE axis over "model" instead
            # (split-KV decode — softmax max/sum all-reduce is tiny, and
            # it avoids all-gathering the cache, ~145 GB/step on llava)
            seq_m = None if kvs else (
                "model" if _divisible(shp[2], msize) else None)
            if batch_ok:
                return P(None, dp, seq_m, kvs, None)
            seq = "data" if _divisible(shp[2], mesh.shape["data"]) \
                else None
            if seq is not None and seq_m is not None:
                return P(None, None, ("data", "model"), kvs, None)
            return P(None, None, seq or seq_m, kvs, None)
        if name == "state":             # [L, B, H, N, P]
            hs = "model" if _divisible(shp[2], msize) else None
            return P(None, dp if batch_ok else None, hs, None, None)
        if name.startswith("conv_"):    # [L, B, K-1, W]
            ws = "model" if _divisible(shp[3], msize) else None
            return P(None, dp if batch_ok else None, None, ws)
        return P(*([None] * len(shp)))

    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(p, l) for p, l in leaves])


def to_shardings(spec_tree: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
