"""Nemotron-H lowering against a plain ``jax.numpy`` reference.

The reference is one period ``MEMEM*E`` of NVIDIA Nemotron-3-Nano
(``hybrid_override_pattern``; HF ``NemotronH`` modelling code) at the
smoke size, in float32 under ``jax.default_matmul_precision("highest")``
on seeded random weights. Each layer is ``h + mixer(rmsnorm(h))``:

* ``M``, the Mamba-2 mixer (arXiv:2405.21060) as its sequential
  recurrence: in_proj to z, x, B, C and dt (one matrix split by column);
  a depthwise causal conv with bias over x, B and C, then SiLU; dt =
  softplus(dt + dt_bias); per head ``S_t = exp(dt_t A) S_{t-1} + dt_t
  B_t x_t^T`` with B and C shared by the heads of a group, ``A =
  -exp(A_log)``; ``y_t = C_t S_t + D x_t``; the gated RMSNorm
  ``rmsnorm(y * silu(z))`` over each of ``n_groups`` groups of the inner
  width; out_proj.
* ``E``, the MoE layer: sigmoid router scores, the top-k chosen on the
  scores plus ``e_score_correction_bias``, gates the chosen scores
  normalised to sum 1 and times ``routed_scaling_factor`` (2.5 in
  config.json); relu^2 experts (up, relu squared, down) and one shared
  expert of its own width, added.
* ``*``, causal GQA attention, scale ``1/sqrt(head_dim)``.

Departures: no embedding, final norm or unembed (outside the lowered
layers); no rotary embedding on attention (the modelling code applies
none; it would be elementwise either way); no capacity drop, as the
published routing is dropless, and the smoke config's capacity factor
``n_experts / top_k`` gives every expert a slot per token.

Elementwise steps absent from the lowering, in the reference and the
chains alike: the block RMSNorms and residual adds, the depthwise conv
and its SiLU, softplus(dt), the decays ``exp(dt A)`` and the inter-chunk
state recurrence, ``D x``, the gated norm, the softmax and causal mask,
the router's sigmoid, top-k and gate normalisation, relu^2 and the
expert combine.

The chains run the lowered matmuls one by one, in the lowering's order
and shapes: at prefill the chunked SSD dual (``ssd_scores``,
``ssd_ydiag``, ``ssd_state``, ``ssd_yoff``) in place of the sequential
scan; at decode one token from the prefilled SSM and conv states and KV
cache. Each matmul is recorded with the outputs it reads and how
(row by row, folded into heads, as a head's stationary operand, or
whole), and checked against the lowered layer and its edges. An
elementwise factor applied after a matmul counts as that matmul's: the
decays and dt that weight ``C B^T`` belong to ``ssd_scores``, the
inter-chunk recurrence to ``ssd_state``; so a read such as ``ssd_yoff``'s
of dt reaches it through a producer that already waits for dt whole.
The residual stream reaches a layer through the same row-aligned chain
of producers.
"""
import math

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.workloads import expert_range, lower, moe_capacity

CFG = get_config("nemotron_3_nano", smoke=True)
PERIOD = CFG.layer_pattern
T = 8                 # prefill tokens: two SSD chunks of 4
SCALING = 2.5         # routed_scaling_factor, config.json
EPS = 1e-5            # layer_norm_epsilon, config.json

#: the chains against the float32 reference, as a share of the largest
#: output: float32 sums of at most 96 terms, and the chunked dual
#: regrouping the scan's sums, read 2.5e-7 to 6e-7 on three seeds; 1e-4
#: leaves a margin over a hundredfold, while bfloat16 matmuls (8-bit
#: mantissa) read 7e-3 to 1e-2 and fail it (checked below)
TOL = 1e-4
#: the expert shares against the whole layer: the same float32 products
#: summed in another grouping (float32 rounding of a regrouped sum is at
#: most ~1e-7 of the largest output)
SHARE_TOL = 1e-5

ROWS = ("identity", 1)     # row for row, column for column
WHOLE = ("full",)          # the whole output first (gather, cache, state)


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _weights(seed=0):
    """Seeded random weights of one period, one dict per layer."""
    c = CFG
    d, di, h = c.d_model, c.d_inner, c.ssm_heads
    gn = c.ssm_groups * c.ssm_state
    hd, kvh = c.hd, c.n_kv_heads
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 200))
    out = []
    for kind in PERIOD:
        w = {"norm": 1.0 + _normal(next(keys), (d,), 0.1)}
        if kind == "M":
            s = 1.0 / math.sqrt(d)
            dt0 = jnp.exp(jax.random.uniform(next(keys), (h,),
                                             minval=math.log(1e-3),
                                             maxval=math.log(1e-1)))
            w.update(
                W_z=_normal(next(keys), (d, di), s),
                W_x=_normal(next(keys), (d, di), s),
                W_B=_normal(next(keys), (d, gn), s),
                W_C=_normal(next(keys), (d, gn), s),
                W_dt=_normal(next(keys), (d, h), s),
                conv_w=_normal(next(keys), (c.ssm_conv, di + 2 * gn), 0.5),
                conv_b=_normal(next(keys), (di + 2 * gn,), 0.1),
                dt_bias=dt0 + jnp.log(-jnp.expm1(-dt0)),  # softplus^-1
                A_log=jnp.log(jax.random.uniform(next(keys), (h,),
                                                 minval=1.0, maxval=16.0)),
                D=1.0 + _normal(next(keys), (h,), 0.1),
                gnorm=1.0 + _normal(next(keys), (di,), 0.1),
                W_out=_normal(next(keys), (di, d), 1.0 / math.sqrt(di)))
        elif kind == "E":
            e, ff, ffs = c.n_experts, c.d_ff, c.d_ff_shared
            w.update(
                W_router=_normal(next(keys), (d, e), 1.0 / math.sqrt(d)),
                bias=_normal(next(keys), (e,), 0.1),
                up=_normal(next(keys), (e, d, ff), 1.0 / math.sqrt(d)),
                down=_normal(next(keys), (e, ff, d), 1.0 / math.sqrt(ff)),
                s_up=_normal(next(keys), (d, ffs), 1.0 / math.sqrt(d)),
                s_down=_normal(next(keys), (ffs, d), 1.0 / math.sqrt(ffs)))
        else:
            s = 1.0 / math.sqrt(d)
            w.update(W_q=_normal(next(keys), (d, c.n_heads * hd), s),
                     W_k=_normal(next(keys), (d, kvh * hd), s),
                     W_v=_normal(next(keys), (d, kvh * hd), s),
                     W_o=_normal(next(keys), (c.n_heads * hd, d),
                                 1.0 / math.sqrt(c.n_heads * hd)))
        out.append(w)
    return out


# ---------------------------------------------------------------------------
# Elementwise pieces, shared by the reference and the chains
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale


def _gated_norm(y, z, scale):
    """``rmsnorm(y * silu(z))`` over each of ``ssm_groups`` groups."""
    g = y * jax.nn.silu(z)
    shp = g.shape
    g = g.reshape(shp[:-1] + (CFG.ssm_groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + EPS)
    return g.reshape(shp) * scale


def _conv(xbc, w, prev):
    """Depthwise causal conv with bias then SiLU over ``xbc`` [T, ch],
    ``prev`` the K-1 inputs before it (zeros at the start)."""
    full = jnp.concatenate([prev, xbc])
    k, n = CFG.ssm_conv, xbc.shape[0]
    out = sum(full[i:i + n] * w["conv_w"][i] for i in range(k))
    return jax.nn.silu(out + w["conv_b"])


def _split_xbc(xbc):
    """Conv output -> x [T, H, P], B and C per head [T, H, N]."""
    c = CFG
    n_t, di, gn = xbc.shape[0], c.d_inner, c.ssm_groups * c.ssm_state
    per = c.ssm_heads // c.ssm_groups
    x = xbc[:, :di].reshape(n_t, c.ssm_heads, c.ssm_head_dim)

    def heads(m):
        return jnp.repeat(m.reshape(n_t, c.ssm_groups, c.ssm_state),
                          per, axis=1)
    return x, heads(xbc[:, di:di + gn]), heads(xbc[:, di + gn:])


def _route(logits, bias):
    """Sigmoid scores, top-k on score + bias, normalised gates x 2.5:
    a dense [T, E] gate matrix (zero where not chosen)."""
    s = jax.nn.sigmoid(logits)
    top = jax.lax.top_k(s + bias, CFG.top_k)[1]
    g = jnp.take_along_axis(s, top, -1)
    g = g / (g.sum(-1, keepdims=True) + 1e-20) * SCALING
    rows = jnp.arange(s.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, top].set(g)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


# ---------------------------------------------------------------------------
# The reference: sequential scan, full causal attention, dense MoE
# ---------------------------------------------------------------------------

def _mamba_ref(w, u):
    c = CFG
    n_t = u.shape[0]
    z = u @ w["W_z"]
    xbc = jnp.concatenate([u @ w["W_x"], u @ w["W_B"], u @ w["W_C"]], -1)
    xbc = _conv(xbc, w, jnp.zeros((c.ssm_conv - 1, xbc.shape[1])))
    x, b, cc = _split_xbc(xbc)
    dt = jax.nn.softplus(u @ w["W_dt"] + w["dt_bias"])      # [T, H]
    a = -jnp.exp(w["A_log"])
    s = jnp.zeros((c.ssm_heads, c.ssm_state, c.ssm_head_dim))
    ys = []
    for t in range(n_t):
        s = jnp.exp(dt[t] * a)[:, None, None] * s \
            + dt[t][:, None, None] * b[t][:, :, None] * x[t][:, None, :]
        ys.append(jnp.einsum("hn,hnp->hp", cc[t], s)
                  + w["D"][:, None] * x[t])
    y = jnp.stack(ys).reshape(n_t, c.d_inner)
    return _gated_norm(y, z, w["gnorm"]) @ w["W_out"]


def _moe_ref(w, u, experts=None):
    """(shared expert's output, routed experts' part) over ``experts``
    (all by default); routing is over every expert."""
    c = CFG
    gates = _route(u @ w["W_router"], w["bias"])
    shared = _relu2(u @ w["s_up"]) @ w["s_down"]
    held = range(c.n_experts) if experts is None else experts
    routed = sum(gates[:, e:e + 1] * (_relu2(u @ w["up"][e]) @ w["down"][e])
                 for e in held)
    return shared, routed


def _attn_ref(w, u):
    c = CFG
    n_t, h, hd = u.shape[0], c.n_heads, c.hd
    grp = h // c.n_kv_heads
    q = (u @ w["W_q"]).reshape(n_t, h, hd)
    k = jnp.repeat((u @ w["W_k"]).reshape(n_t, -1, hd), grp, axis=1)
    v = jnp.repeat((u @ w["W_v"]).reshape(n_t, -1, hd), grp, axis=1)
    s = jnp.einsum("thd,jhd->htj", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((n_t, n_t), bool)), s, -jnp.inf)
    o = jnp.einsum("htj,jhd->thd", jax.nn.softmax(s, -1), v)
    return o.reshape(n_t, h * hd) @ w["W_o"]


def reference(ws, x):
    """The period's residual stream after its last layer, for ``x``
    [T, d]."""
    h = x
    with jax.default_matmul_precision("highest"):
        for kind, w in zip(PERIOD, ws):
            u = _rmsnorm(h, w["norm"])
            if kind == "M":
                h = h + _mamba_ref(w, u)
            elif kind == "E":
                h = h + sum(_moe_ref(w, u))
            else:
                h = h + _attn_ref(w, u)
    return h


# ---------------------------------------------------------------------------
# The chains: the lowered matmuls one by one
# ---------------------------------------------------------------------------

class Chain:
    """Runs matmuls one at a time and records each as the lowering names
    it: (name, M, K, N, batch, reads), reads mapping each earlier output
    it reads to the coordinate-map key of how it reads it."""

    def __init__(self, dtype=jnp.float32):
        self.dtype, self.ops, self.out = dtype, [], {}

    def mm(self, name, a, b, reads, batch=1):
        """``a @ b`` (batched when ``batch > 1``), in ``dtype``."""
        m, k = a.shape[-2:]
        how = dict((r, ROWS) if isinstance(r, str) else r for r in reads)
        self.ops.append((name, m, k, b.shape[-1], batch, how))
        with jax.default_matmul_precision("highest"):
            y = jnp.matmul(a.astype(self.dtype), b.astype(self.dtype))
        self.out[name] = y.astype(jnp.float32)
        return self.out[name]


def _mamba_chain(ch, w, u, pre, reads, state):
    """One Mamba-2 layer; ``state`` (conv inputs, SSM state) is read at
    decode and written at prefill. Returns (output, its readers' view)."""
    c = CFG
    n_t = u.shape[0]
    h, p, n = c.ssm_heads, c.ssm_head_dim, c.ssm_state
    z = ch.mm(pre + "z_proj", u, w["W_z"], reads)
    xbc = jnp.concatenate([ch.mm(pre + "x_proj", u, w["W_x"], reads),
                           ch.mm(pre + "b_proj", u, w["W_B"], reads),
                           ch.mm(pre + "c_proj", u, w["W_C"], reads)], -1)
    dt = jax.nn.softplus(ch.mm(pre + "dt_proj", u, w["W_dt"], reads)
                         + w["dt_bias"])
    a = -jnp.exp(w["A_log"])
    whole = [(pre + k, WHOLE) for k in ("b_proj", "x_proj", "dt_proj")]
    if state.get("ssm") is None:                    # prefill, chunked
        prev = jnp.zeros((c.ssm_conv - 1, xbc.shape[1]))
        x, b, cc = _split_xbc(_conv(xbc, w, prev))
        state["conv"] = jnp.concatenate([prev, xbc])[-(c.ssm_conv - 1):]
        ck = min(c.ssm_chunk, n_t)
        nc = n_t // ck

        def chunks(m):              # [T, H, k] -> [nc, H, ck, k]
            return m.reshape(nc, ck, h, -1).transpose(0, 2, 1, 3)
        xs, bs, cs = chunks(x), chunks(b), chunks(cc)
        cum = jnp.cumsum((dt * a).reshape(nc, ck, h).transpose(0, 2, 1),
                         -1)                                  # [nc, H, ck]
        dts = dt.reshape(nc, ck, h).transpose(0, 2, 1)
        sc = ch.mm(pre + "ssd_scores", cs.reshape(nc * h, ck, n),
                   bs.reshape(nc * h, ck, n).transpose(0, 2, 1),
                   [(pre + "c_proj", WHOLE), (pre + "b_proj", WHOLE),
                    (pre + "dt_proj", WHOLE)], batch=nc * h)
        seg = cum[..., :, None] - cum[..., None, :]
        lmask = jnp.where(jnp.tril(jnp.ones((ck, ck), bool)),
                          jnp.exp(seg), 0.0) * dts[..., None, :]
        y_diag = ch.mm(pre + "ssd_ydiag", sc * lmask.reshape(nc * h, ck, ck),
                       xs.reshape(nc * h, ck, p),
                       [pre + "ssd_scores", (pre + "x_proj", WHOLE)],
                       batch=nc * h)
        to_end = jnp.exp(cum[..., -1:] - cum) * dts           # [nc, H, ck]
        st = ch.mm(pre + "ssd_state",
                   bs.reshape(nc * h, ck, n).transpose(0, 2, 1),
                   (xs * to_end[..., None]).reshape(nc * h, ck, p), whole,
                   batch=nc * h).reshape(nc, h, n, p)
        s_in = [jnp.zeros((h, n, p))]
        for k in range(nc):         # the inter-chunk recurrence
            s_in.append(jnp.exp(cum[k, :, -1])[:, None, None] * s_in[-1]
                        + st[k])
        state["ssm"] = s_in[-1]
        y_off = ch.mm(pre + "ssd_yoff", cs.reshape(nc * h, ck, n),
                      jnp.stack(s_in[:-1]).reshape(nc * h, n, p),
                      [(pre + "c_proj", WHOLE), (pre + "ssd_state", WHOLE)],
                      batch=nc * h).reshape(nc, h, ck, p)
        y = y_diag.reshape(nc, h, ck, p) + jnp.exp(cum)[..., None] * y_off
        y = y.transpose(0, 2, 1, 3).reshape(n_t, h, p)
        ys = [pre + "ssd_ydiag", pre + "ssd_yoff"]
    else:                                           # decode, one token
        x, b, cc = _split_xbc(_conv(xbc, w, state["conv"]))
        upd = ch.mm(pre + "ssd_state", b[0][:, :, None],
                    (dt[0][:, None] * x[0])[:, None, :], whole, batch=h)
        s = jnp.exp(dt[0] * a)[:, None, None] * state["ssm"] + upd
        y = ch.mm(pre + "ssd_y", cc[0][:, None, :], s,
                  [(pre + "c_proj", WHOLE), (pre + "ssd_state", WHOLE)],
                  batch=h).reshape(1, h, p)
        ys = [pre + "ssd_y"]
    y = (y + w["D"][:, None] * x).reshape(n_t, c.d_inner)
    out = ch.mm(pre + "out_proj", _gated_norm(y, z, w["gnorm"]), w["W_out"],
                [(r, WHOLE) for r in ys] + [(pre + "z_proj", WHOLE)])
    return out, [pre + "out_proj"]


def _moe_chain(ch, w, u, pre, reads, experts=None):
    """One MoE layer: router, the shared expert, then each held routed
    expert over its capacity slots (the tokens routed to it, in order)."""
    c = CFG
    n_t = u.shape[0]
    gates = _route(ch.mm(pre + "router", u, w["W_router"], reads),
                   w["bias"])
    f1 = ch.mm(pre + "shared0.ffn1", u, w["s_up"], reads)
    out = ch.mm(pre + "shared0.ffn2", _relu2(f1), w["s_down"],
                [pre + "shared0.ffn1"])
    readers = [pre + "shared0.ffn2"]
    cap = moe_capacity(CFG, n_t)
    gather = [(pre + "router", WHOLE)] + [
        (r if isinstance(r, str) else r[0], WHOLE) for r in reads]
    held = range(c.n_experts) if experts is None else experts
    for e in held:
        toks = [t for t in range(n_t) if float(gates[t, e]) != 0.0]
        assert len(toks) <= cap           # dropless at this capacity
        slots = jnp.zeros((cap, c.d_model))
        if toks:
            slots = slots.at[:len(toks)].set(u[jnp.array(toks)])
        e1 = ch.mm(f"{pre}exp{e}.ffn1", slots, w["up"][e], gather)
        e2 = ch.mm(f"{pre}exp{e}.ffn2", _relu2(e1), w["down"][e],
                   [f"{pre}exp{e}.ffn1"])
        for j, t in enumerate(toks):      # the combine, a scatter-add
            out = out.at[t].add(gates[t, e] * e2[j])
        readers.append((f"{pre}exp{e}.ffn2", WHOLE))
    return out, readers


def _attn_chain(ch, w, u, pre, reads, cache):
    """GQA attention; ``cache`` (K, V) is read at decode, written at
    prefill."""
    c = CFG
    n_t, h, hd = u.shape[0], c.n_heads, c.hd
    grp = h // c.n_kv_heads
    q = ch.mm(pre + "q_proj", u, w["W_q"], reads)
    k = ch.mm(pre + "k_proj", u, w["W_k"], reads)
    v = ch.mm(pre + "v_proj", u, w["W_v"], reads)
    if cache.get("k") is None:                      # prefill
        cache["k"], cache["v"] = k, v
        kq = (pre + "k_proj", ("weight", "qk_weight", n_t, hd, grp))
        vq = (pre + "v_proj", ("weight", "av_weight", n_t, hd, grp))
    else:                                           # the cache append
        k = cache["k"] = jnp.concatenate([cache["k"], k])
        v = cache["v"] = jnp.concatenate([cache["v"], v])
        kq, vq = (pre + "k_proj", WHOLE), (pre + "v_proj", WHOLE)
    n_kv = k.shape[0]

    def heads(m, n):              # [n, kvh*hd] -> [h, n, hd]
        return jnp.repeat(m.reshape(n, -1, hd), grp, 1).transpose(1, 0, 2)
    s = ch.mm(pre + "qk", q.reshape(n_t, h, hd).transpose(1, 0, 2),
              heads(k, n_kv).transpose(0, 2, 1),
              [(pre + "q_proj", ("headfold", n_t, hd)), kq], batch=h)
    mask = jnp.arange(n_kv)[None, :] <= jnp.arange(n_t)[:, None] \
        + (n_kv - n_t)
    p = jax.nn.softmax(jnp.where(mask, s / math.sqrt(hd), -jnp.inf), -1)
    av = ch.mm(pre + "av", p, heads(v, n_kv), [pre + "qk", vq], batch=h)
    out = ch.mm(pre + "out_proj", av.transpose(1, 0, 2).reshape(n_t, h * hd),
                w["W_o"], [(pre + "av", ("headunfold", n_t, hd))])
    return out, [pre + "out_proj"]


def chain(ws, x, states=None, dtype=jnp.float32, experts=None):
    """The lowered period over ``x``: prefill when ``states`` is None
    (returns the states it leaves), else one decode token through them.
    Returns (residual stream, chain, states)."""
    states = states if states is not None else [{} for _ in PERIOD]
    ch = Chain(dtype)
    h, reads = x, []
    for i, (kind, w, st) in enumerate(zip(PERIOD, ws, states)):
        pre = f"b{i}."
        u = _rmsnorm(h, w["norm"])
        if kind == "M":
            y, reads = _mamba_chain(ch, w, u, pre, reads, st)
        elif kind == "E":
            y, reads = _moe_chain(ch, w, u, pre, reads, experts)
        else:
            y, reads = _attn_chain(ch, w, u, pre, reads, st)
        h = h + y
    return h, ch, states


def _inputs(seed=1, n=T):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, CFG.d_model),
                             jnp.float32)


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def test_smoke_config_keeps_the_structure():
    """The smoke size keeps what the published config forces."""
    c, full = CFG, get_config("nemotron_3_nano")
    assert PERIOD == full.layer_pattern[:7] == "MEMEM*E"
    for cfg in (c, full):
        assert cfg.d_inner == cfg.ssm_n_heads * cfg.ssm_head_dim \
            != cfg.ssm_expand * cfg.d_model
        assert cfg.d_ff_shared != cfg.d_ff and cfg.mlp == "relu2"
        assert cfg.n_heads // cfg.n_kv_heads > 1
        assert cfg.hd != cfg.d_model // cfg.n_heads
    assert c.n_experts == 8 and moe_capacity(c, T) == T
    assert (full.d_inner, full.ssm_heads, full.d_ff_shared) == \
        (4096, 64, 3712)


@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_chain_matches_reference(seed):
    """The chunked SSD dual and the lowered attention and MoE matmuls
    give the sequential reference's period."""
    ws, x = _weights(seed), _inputs(seed + 10)
    y, _, _ = chain(ws, x)
    assert _rel(y, reference(ws, x)) <= TOL


@pytest.mark.parametrize("seed", [0, 1])
def test_decode_from_prefilled_state_matches_full_forward(seed):
    """Prefill T tokens, then decode token T+1 through the conv and SSM
    states and the KV cache: the step's output is the full forward's
    last position."""
    ws, x = _weights(seed), _inputs(seed + 10, T + 1)
    _, _, states = chain(ws, x[:T])
    y, _, _ = chain(ws, x[T:], states)
    assert _rel(y[0], reference(ws, x)[T]) <= TOL


def test_bfloat16_matmuls_fail_the_tolerance():
    """The tolerance is tight enough to see a lower precision."""
    ws, x = _weights(0), _inputs(10, T + 1)
    ref = reference(ws, x)
    y, _, _ = chain(ws, x[:T], dtype=jnp.bfloat16)
    assert _rel(y, ref[:T]) > TOL
    _, _, states = chain(ws, x[:T])
    y, _, _ = chain(ws, x[T:], states, dtype=jnp.bfloat16)
    assert _rel(y[0], ref[T]) > TOL


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_chain_matmuls_are_the_lowered_layers_in_order(phase):
    """Every matmul of the chain is the lowered layer at its position,
    of the same name and (M, K, N, batch), and the layer's edges read
    exactly the outputs the matmul reads, through the coordinate map of
    the way it reads them."""
    ws = _weights(0)
    _, ch, states = chain(ws, _inputs(10))
    if phase == "prefill":
        layers, edges = lower(CFG, "prefill", seq=T, blocks=len(PERIOD))
    else:
        _, ch, _ = chain(ws, _inputs(11, 1), states)
        layers, edges = lower(CFG, "decode", kv_len=T + 1,
                              blocks=len(PERIOD))
    assert len(ch.ops) == len(layers)
    for i, (name, m, k, n, batch, reads) in enumerate(ch.ops):
        lay = layers[i]
        assert lay.name == name
        assert (lay.K, lay.C, lay.P, lay.Q, lay.R, lay.S) == \
            (n, k, m * batch, 1, 1, 1)
        assert {layers[e.producer].name: e.cmap.key()
                for e in edges[i]} == reads


# ---------------------------------------------------------------------------
# Expert shares: the ``_ep<N>`` lowering against the whole MoE layer
# ---------------------------------------------------------------------------

def _share_experts(n, s):
    """The routed experts the ``share=(s, n)`` lowering holds, read from
    its layer names."""
    layers, _ = lower(CFG, "decode", kv_len=16, blocks=len(PERIOD),
                      share=(s, n))
    return sorted({int(l.name.split(".exp")[1].split(".")[0])
                   for l in layers if ".exp" in l.name})


@pytest.mark.parametrize("n", [2, 4])
def test_expert_shares_sum_to_the_whole_layer(n):
    """The n shares' routed parts, with the shared expert counted once,
    add up to the uncut layer; routing is over every expert in each."""
    w = _weights(3)[1]
    u = jax.random.normal(jax.random.PRNGKey(4), (9, CFG.d_model))
    with jax.default_matmul_precision("highest"):
        shared, whole = _moe_ref(w, u)
        parts = []
        for s in range(n):
            held = _share_experts(n, s)
            assert held == list(expert_range(CFG, (s, n)))
            parts.append(_moe_ref(w, u, held)[1])
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    assert _rel(shared + sum(parts), shared + whole) <= SHARE_TOL


def test_decode_chain_with_an_expert_share_is_the_share_lowering():
    """A device holding one of four shares runs exactly the share
    lowering's matmuls, in its order."""
    ws, x = _weights(0), _inputs(10, T + 1)
    _, _, states = chain(ws, x[:T])
    held = list(expert_range(CFG, (1, 4)))
    _, ch, _ = chain(ws, x[T:], [dict(s) for s in states], experts=held)
    layers, _ = lower(CFG, "decode", kv_len=T + 1, blocks=len(PERIOD),
                      share=(1, 4))
    assert [op[0] for op in ch.ops] == [l.name for l in layers]


def test_mapping_service_answers_a_nemotron_request():
    """The scenario goes through the normal entry point: a
    ``MappingRequest`` names it, the service answers with a search; a
    pattern read past its end is refused at the request."""
    from repro.serve import MappingRequest, MappingService
    assert MappingRequest(network="nemotron_3_nano_ep16:decode@32768x7")
    with pytest.raises(ValueError):
        MappingRequest(network="nemotron_3_nano_smoke:decode@16x8")
    svc = MappingService()
    try:
        resp = svc.request(MappingRequest(
            network="nemotron_3_nano_smoke_ep4:decode@16x7",
            explorer="grid", budget=1, n_candidates=2, max_steps=128,
            seed=0))
    finally:
        svc.close()
    assert resp.served_from == "search" and resp.evaluated == 1
    assert resp.best["total_ns"] > 0
