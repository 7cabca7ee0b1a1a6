"""DeepSeek-V2 lowering against a plain ``jax.numpy`` reference.

The reference follows arXiv:2405.04434: multi-head latent attention from
Sec. 2.1 and the full formulas of its Appendix C, in the decompressed
form, and the DeepSeekMoE layer of Sec. 2.2 with group-limited routing.
It runs in float32 under ``jax.default_matmul_precision("highest")`` on
seeded random weights at the smoke size. Departures, each elementwise
and so absent from the lowering as well:

* plain RoPE (base 10000) in place of YaRN, and the softmax scale
  ``1/sqrt(qk_nope_head_dim + qk_rope_head_dim)`` without YaRN's mscale;
* no RMSNorm on the query and key/value latents (the released code has
  them; the paper's equations do not) and no block norms or residuals;
* no ``routed_scaling_factor`` on the routed experts' gates.

The chains below run the lowered matmuls one by one, in the lowering's
order and with its shapes: the decompressed chain at prefill and the
absorbed chain at decode, where W^UK is folded into the query and W^UV
into the output and the step attends over the latent cache. Each matmul
is recorded with the outputs it reads and how it reads them (row by row,
folded into heads, as a head's stationary operand, or whole through the
cache), so the lowered layers and edges are checked against the
arithmetic they stand for.
"""
import math

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.workloads import expert_range, lower, moe_capacity

CFG = get_config("deepseek_v2", smoke=True)
T = 7        # prefill tokens of the full forward; decode appends token T

#: the chains against the float32 reference, as a share of the largest
#: output: float32 sums of at most 96 terms taken in another order (the
#: absorbed chain multiplies W^UK and W^UV in another order) read 1e-7 to
#: 4e-7 on three seeds; 1e-4 leaves over a hundredfold margin, while
#: bfloat16 matmuls (8-bit mantissa) read 5e-3 to 6e-3 and fail it
#: (checked below)
TOL = 1e-4
#: the expert shares against the whole layer: the same float32 products
#: summed in another grouping (read 0 at this size; float32 rounding of
#: a regrouped sum is at most ~1e-7 of the largest output)
SHARE_TOL = 1e-5


def _weights(seed=0):
    c = CFG
    h, r = c.n_heads, c.qk_rope_head_dim
    nope, vd, lat = c.qk_nope_head_dim, c.v_head_dim, c.kv_lora_rank
    shapes = {"W_DQ": (c.d_model, c.q_lora_rank),
              "W_UQ": (c.q_lora_rank, h * nope),
              "W_QR": (c.q_lora_rank, h * r),
              "W_DKV": (c.d_model, lat),
              "W_KR": (c.d_model, r),
              "W_UK": (lat, h * nope),
              "W_UV": (lat, h * vd),
              "W_O": (h * vd, c.d_model)}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {n: jax.random.normal(k, s, jnp.float32) / math.sqrt(s[0])
            for k, (n, s) in zip(keys, shapes.items())}


def _rope(x, pos):
    """Plain RoPE over the last dim (split halves), positions ``pos``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 3:                       # [T, H, r]
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla_reference(w, x):
    """Causal MLA over ``x`` [T, d] (Appendix C), decompressed: per-head
    queries and keys are the concatenations [q^C; q^R] and [k^C; k^R]."""
    c = CFG
    n, h = x.shape[0], c.n_heads
    nope, r, vd = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    pos = jnp.arange(n)
    with jax.default_matmul_precision("highest"):
        c_q = x @ w["W_DQ"]
        q_c = (c_q @ w["W_UQ"]).reshape(n, h, nope)
        q_r = _rope((c_q @ w["W_QR"]).reshape(n, h, r), pos)
        c_kv = x @ w["W_DKV"]
        k_c = (c_kv @ w["W_UK"]).reshape(n, h, nope)
        k_r = _rope(x @ w["W_KR"], pos)
        v_c = (c_kv @ w["W_UV"]).reshape(n, h, vd)
        q = jnp.concatenate([q_c, q_r], -1)
        k = jnp.concatenate([k_c, jnp.broadcast_to(k_r[:, None, :],
                                                   (n, h, r))], -1)
        s = jnp.einsum("thd,jhd->htj", q, k) / math.sqrt(nope + r)
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
        o = jnp.einsum("htj,jhd->thd", jax.nn.softmax(s, -1), v_c)
        return o.reshape(n, h * vd) @ w["W_O"]


ROWS = ("identity", 1)     # row for row, column for column
WHOLE = ("full",)          # through the cache: the whole fresh output


class Chain:
    """Runs matmuls one at a time and records each as the lowering names
    it: (name, M, K, N, batch) and how it reads which earlier outputs
    (a name alone reads ``ROWS``; else (name, coordinate-map key))."""

    def __init__(self, dtype=jnp.float32):
        self.dtype, self.ops, self.out = dtype, [], {}

    def mm(self, name, a, b, reads, batch=1):
        """``a @ b`` (head-batched when ``batch > 1``), in ``dtype``."""
        m, k = a.shape[-2:]
        how = dict((r, ROWS) if isinstance(r, str) else r for r in reads)
        self.ops.append((name, m, k, b.shape[-1], batch, how))
        with jax.default_matmul_precision("highest"):
            y = jnp.matmul(a.astype(self.dtype), b.astype(self.dtype))
        self.out[name] = y.astype(jnp.float32)
        return self.out[name]


def _projections(ch, w, x):
    """The five down/up projections both phases share."""
    q_down = ch.mm("q_down", x, w["W_DQ"], ())
    ch.mm("q_nope_up", q_down, w["W_UQ"], ["q_down"])
    ch.mm("q_rope_up", q_down, w["W_QR"], ["q_down"])
    ch.mm("kv_down", x, w["W_DKV"], ())
    ch.mm("k_rope", x, w["W_KR"], ())


def prefill_chain(w, x, dtype=jnp.float32):
    """The lowered prefill sublayer; returns (output, chain, latent
    cache), the cache being ``kv_down`` and the rotated ``k_rope``."""
    c = CFG
    n, h = x.shape[0], c.n_heads
    nope, r, vd = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    pos = jnp.arange(n)
    ch = Chain(dtype)
    _projections(ch, w, x)
    o = ch.out
    k_up = ch.mm("k_up", o["kv_down"], w["W_UK"], ["kv_down"])
    v_up = ch.mm("v_up", o["kv_down"], w["W_UV"], ["kv_down"])

    def heads(y, d):                     # [n, h*d] -> [h, n, d]
        return y.reshape(n, h, d).transpose(1, 0, 2)

    k_r = _rope(o["k_rope"], pos)
    s_nope = ch.mm("qk_nope", heads(o["q_nope_up"], nope),
                   heads(k_up, nope).transpose(0, 2, 1),
                   [("q_nope_up", ("headfold", n, nope)),
                    ("k_up", ("weight", "qk_weight", n, nope, 1))],
                   batch=h)
    q_r = _rope(o["q_rope_up"].reshape(n, h, r), pos).transpose(1, 0, 2)
    s_rope = ch.mm("qk_rope", q_r, jnp.broadcast_to(k_r.T, (h, r, n)),
                   [("q_rope_up", ("headfold", n, r)),
                    # one rope key, shared by all h heads
                    ("k_rope", ("weight", "qk_weight", n, r, h))],
                   batch=h)
    s = (s_nope + s_rope) / math.sqrt(nope + r)
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    av = ch.mm("av", jax.nn.softmax(s, -1), heads(v_up, vd),
               ["qk_nope", "qk_rope",
                ("v_up", ("weight", "av_weight", n, vd, 1))], batch=h)
    y = ch.mm("o_proj", av.transpose(1, 0, 2).reshape(n, h * vd),
              w["W_O"], [("av", ("headunfold", n, vd))])
    return y, ch, (o["kv_down"], k_r)


def decode_chain(w, x_t, cache, dtype=jnp.float32):
    """The lowered absorbed decode sublayer for one token ``x_t`` [1, d]
    at position len(cache): the fresh latent and rope key are appended
    to the cache and every head attends over the latents themselves."""
    c = CFG
    h, nope, r = c.n_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
    lat, vd = c.kv_lora_rank, c.v_head_dim
    c_kv, k_r = cache
    pos = jnp.array([c_kv.shape[0]])
    ch = Chain(dtype)
    _projections(ch, w, x_t)
    o = ch.out
    c_kv = jnp.concatenate([c_kv, o["kv_down"]])          # cache append
    k_r = jnp.concatenate([k_r, _rope(o["k_rope"], pos)])
    w_uk = w["W_UK"].reshape(lat, h, nope).transpose(1, 2, 0)  # [h,nope,lat]
    w_uv = w["W_UV"].reshape(lat, h, vd).transpose(1, 0, 2)    # [h,lat,vd]
    q_abs = ch.mm("q_absorb", o["q_nope_up"].reshape(h, 1, nope), w_uk,
                  [("q_nope_up", ("headfold", 1, nope))], batch=h)
    s_lat = ch.mm("qk_lat", q_abs, jnp.broadcast_to(c_kv.T, (h,) + c_kv.T
                                                    .shape),
                  ["q_absorb", ("kv_down", WHOLE)], batch=h)
    q_r = _rope(o["q_rope_up"].reshape(1, h, r), pos).reshape(h, 1, r)
    s_rope = ch.mm("qk_rope", q_r, jnp.broadcast_to(k_r.T, (h,) + k_r.T
                                                    .shape),
                   [("q_rope_up", ("headfold", 1, r)), ("k_rope", WHOLE)],
                   batch=h)
    p = jax.nn.softmax((s_lat + s_rope) / math.sqrt(nope + r), -1)
    av = ch.mm("av_lat", p, jnp.broadcast_to(c_kv, (h,) + c_kv.shape),
               ["qk_lat", "qk_rope", ("kv_down", WHOLE)], batch=h)
    v = ch.mm("v_up", av, w_uv, ["av_lat"], batch=h)
    y = ch.mm("o_proj", v.reshape(1, h * vd), w["W_O"],
              [("v_up", ("headunfold", 1, vd))])
    return y, ch


def _inputs(seed=1, n=T):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, CFG.d_model),
                             jnp.float32)


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_chain_matches_reference(seed):
    w, x = _weights(seed), _inputs(seed + 10)
    y, _, _ = prefill_chain(w, x)
    assert _rel(y, mla_reference(w, x)) <= TOL


@pytest.mark.parametrize("seed", [0, 1])
def test_absorbed_decode_over_prefilled_cache_matches_full_forward(seed):
    """Prefill T tokens, then decode token T+1 through the latent cache:
    the step's output is the full forward's last position."""
    w, x = _weights(seed), _inputs(seed + 10, T + 1)
    _, _, cache = prefill_chain(w, x[:T])
    y, _ = decode_chain(w, x[T:], cache)
    assert _rel(y[0], mla_reference(w, x)[T]) <= TOL


def test_bfloat16_matmuls_fail_the_tolerance():
    """The tolerance is tight enough to see a lower precision."""
    w, x = _weights(0), _inputs(10, T + 1)
    ref = mla_reference(w, x)
    y, _, cache = prefill_chain(w, x[:T], jnp.bfloat16)
    assert _rel(y, ref[:T]) > TOL
    _, _, cache = prefill_chain(w, x[:T])
    y, _ = decode_chain(w, x[T:], cache, jnp.bfloat16)
    assert _rel(y[0], ref[T]) > TOL


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_chain_matmuls_are_the_lowered_layers_in_order(phase):
    """Every matmul of the chain has a lowered layer of the same name,
    (M, K, N, batch) and position, and the layer's edges read exactly
    the outputs the chain's matmul reads, through the coordinate map of
    the way it reads them."""
    w = _weights(0)
    if phase == "prefill":
        _, ch, _ = prefill_chain(w, _inputs(10))
        layers, edges = lower(CFG, "prefill", seq=T)
    else:
        _, _, cache = prefill_chain(w, _inputs(10))
        _, ch = decode_chain(w, _inputs(11, 1), cache)
        layers, edges = lower(CFG, "decode", kv_len=T + 1)
    assert len(ch.ops) == 11
    for i, (name, m, k, n, batch, reads) in enumerate(ch.ops):
        lay = layers[i]
        assert lay.name == name
        assert (lay.K, lay.C, lay.P, lay.Q, lay.R, lay.S) == \
            (n, k, m * batch, 1, 1, 1)
        assert {layers[e.producer].name: e.cmap.key()
                for e in edges[i]} == reads
    assert layers[len(ch.ops)].name == "ffn_gate"   # the sublayer ends


# ---------------------------------------------------------------------------
# Expert shares: the ``_ep<N>`` lowering against the whole MoE layer
# ---------------------------------------------------------------------------

def _moe_weights(seed=3):
    c = CFG
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)

    def ffn(k, n):
        a, b, d = jax.random.split(k, 3)
        s = 1.0 / math.sqrt(c.d_model)
        return (jax.random.normal(a, (n, c.d_model, c.d_ff)) * s,
                jax.random.normal(b, (n, c.d_model, c.d_ff)) * s,
                jax.random.normal(d, (n, c.d_ff, c.d_model))
                / math.sqrt(c.d_ff))
    return {"router": jax.random.normal(ks[0], (c.d_model, c.n_experts))
            / math.sqrt(c.d_model),
            "routed": ffn(ks[1], c.n_experts),
            "shared": ffn(ks[2], c.n_shared_experts)}


def _swiglu(p, i, u):
    gate, up, down = p
    return (jax.nn.silu(u @ gate[i]) * (u @ up[i])) @ down[i]


def moe_parts(w, u, experts):
    """DeepSeekMoE (Sec. 2.2) split into what is computed alike on every
    device (the shared experts) and the routed experts ``experts``' part.
    Routing is over all experts: softmax affinities, the ``topk_groups``
    groups of highest best affinity (group-limited greedy), then the
    ``top_k`` experts within them; gates are the affinities."""
    c = CFG
    with jax.default_matmul_precision("highest"):
        s = jax.nn.softmax(u @ w["router"], -1)                 # [T, E]
        grp = s.reshape(u.shape[0], c.n_expert_groups, -1)
        keep = jax.lax.top_k(grp.max(-1), c.topk_groups)[1]
        gmask = jnp.zeros(grp.shape[:2], bool).at[
            jnp.arange(u.shape[0])[:, None], keep].set(True)
        masked = jnp.where(jnp.repeat(gmask, grp.shape[-1], -1), s, -1.0)
        top = jax.lax.top_k(masked, c.top_k)[1]
        gates = jnp.zeros_like(s).at[
            jnp.arange(u.shape[0])[:, None], top].set(
                jnp.take_along_axis(s, top, -1))
        shared = sum(_swiglu(w["shared"], i, u)
                     for i in range(c.n_shared_experts))
        routed = sum(gates[:, e:e + 1] * _swiglu(w["routed"], e, u)
                     for e in experts)
    return shared, routed


def _share_experts(n, s):
    """The routed experts the ``share=(s, n)`` lowering holds, read from
    its layer names."""
    layers, _ = lower(CFG, "decode", kv_len=16, blocks=2, share=(s, n))
    return sorted({int(l.name.split(".exp")[1].split(".")[0])
                   for l in layers if ".exp" in l.name})


def test_expert_shares_sum_to_the_whole_layer():
    """The n_expert_groups shares' routed parts, with the shared experts
    counted once, add up to the uncut layer; each share is one group."""
    n = CFG.n_expert_groups
    w = _moe_weights()
    u = jax.random.normal(jax.random.PRNGKey(4), (9, CFG.d_model))
    shared, whole = moe_parts(w, u, range(CFG.n_experts))
    parts = []
    for s in range(n):
        held = _share_experts(n, s)
        assert held == list(expert_range(CFG, (s, n)))
        parts.append(moe_parts(w, u, held)[1])
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    total = shared + sum(parts)
    assert _rel(total, shared + whole) <= SHARE_TOL


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_share_lowerings_partition_the_routed_experts(phase):
    """Across the shares, routed-expert layers partition the uncut
    lowering's (MACs sum); everything else is the uncut layer's, router
    width and capacity included."""
    n = CFG.n_expert_groups
    kw = dict(seq=32) if phase == "prefill" else dict(kv_len=16)
    whole, _ = lower(CFG, phase, blocks=2, **kw)
    split = {l.name: l for l in whole if ".exp" in l.name}
    rest = [l for l in whole if ".exp" not in l.name]
    seen = {}
    for s in range(n):
        layers, _ = lower(CFG, phase, blocks=2, share=(s, n), **kw)
        mine = [l for l in layers if ".exp" in l.name]
        assert [l for l in layers if ".exp" not in l.name] == rest
        for l in mine:
            assert l.name not in seen and split[l.name] == l
            seen[l.name] = l
    assert seen.keys() == split.keys()
    assert sum(l.macs for l in seen.values()) == \
        sum(l.macs for l in split.values())
    router = next(l for l in rest if l.name.endswith("router"))
    assert router.K == CFG.n_experts
    tokens = 32 if phase == "prefill" else 1
    assert {l.P for l in split.values()} == {moe_capacity(CFG, tokens)}


def test_share_must_divide_the_experts():
    with pytest.raises(ValueError):
        lower(CFG, "decode", kv_len=16, blocks=2, share=(0, 3))
    with pytest.raises(ValueError):
        lower(get_config("olmo_1b", smoke=True), "decode", share=(0, 2))


def test_mapping_service_answers_an_expert_share_request():
    """The scenario goes through the normal entry point: a
    ``MappingRequest`` names it, and the service answers with a search
    (``run_dse`` underneath); a share that does not divide the experts
    is refused at the request."""
    from repro.serve import MappingRequest, MappingService
    assert MappingRequest(network="deepseek_v2_ep8:decode@32768x5")
    with pytest.raises(ValueError):
        MappingRequest(network="deepseek_v2_ep7:decode@32768x5")
    svc = MappingService()
    try:
        resp = svc.request(MappingRequest(
            network="deepseek_v2_smoke_ep2:decode@16x2", explorer="grid",
            budget=1, n_candidates=2, max_steps=128, seed=0))
    finally:
        svc.close()
    assert resp.served_from == "search" and resp.evaluated == 1
    assert resp.best["total_ns"] > 0
