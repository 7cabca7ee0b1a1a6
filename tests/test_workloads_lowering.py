"""LLM workload lowering: differential engine-vs-reference pinning,
golden MAC accounting, and structural property tests.

The lowering layer (``repro.workloads``) introduces new coordinate maps
(``FullMap``, grouped ``WeightMap``) and new network topologies (MoE
fan-out, SSD batched matmuls, cross-attention). Three things must hold:

* **Differential**: the batched engine and the reference path
  (``optimize_network_reference``) produce bit-identical ``NetworkResult``s on
  every zoo smoke config x {prefill, decode} — the engine equivalence
  contract extended over the whole lowered zoo, and over every (mode,
  objective) pair on one MoE and one SSM representative.
* **Golden MACs**: ``sum(l.macs)`` of a lowered block equals the
  analytic per-block FLOP count derived independently from the
  ``ModelConfig`` (exclusions per DESIGN.md Section 15: norms, softmax,
  RoPE, activations, router gate, depthwise convs, residuals,
  embeddings).
* **Invariants**: edges only point backward at valid producers, decode
  shapes never depend on any prefill length, matmul-only chains never
  trigger pool inference, and the new maps agree with OverlaPIM's
  exhaustive overlap analysis (the C2 oracle).
"""
import dataclasses
import hashlib
import json
import math
import random

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import ARCH_IDS, get_config
from repro.core import (FullMap, IdentityMap, SearchConfig, WeightMap,
                        describe, dram_pim, matmul, optimize_network,
                        optimize_network_reference, random_mapping,
                        ready_steps_analytical, ready_steps_exhaustive)
from repro.core.search import MODES, OBJECTIVES
from repro.workloads import lower, moe_capacity, parse_scenario

SMOKE_ARCHS = [a + "_smoke" for a in ARCH_IDS]


def small_arch():
    return dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=64)


def cfg(**kw):
    base = dict(n_candidates=3, seed=7, max_steps=128, mode="transform")
    base.update(kw)
    return SearchConfig(**base)


def assert_results_identical(a, b):
    assert a.total_ns == b.total_ns
    assert a.per_layer_ns == b.per_layer_ns
    assert a.objective == b.objective
    assert a.total_energy_pj == b.total_energy_pj
    assert a.summary() == b.summary()
    for la, lb in zip(a.layers, b.layers):
        assert la.mapping.blocks == lb.mapping.blocks
        assert la.start_ns == lb.start_ns and la.end_ns == lb.end_ns
        assert np.array_equal(la.finish_ns, lb.finish_ns)
        assert la.transformed == lb.transformed
        assert la.moved_frac == lb.moved_frac
        assert la.moved_bytes == lb.moved_bytes
        assert la.move_energy_pj == lb.move_energy_pj


# ---------------------------------------------------------------------------
# Differential: engine == reference over the whole lowered zoo
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("phase", ["prefill", "decode"])
@pytest.mark.parametrize("arch_id", SMOKE_ARCHS)
def test_engine_matches_reference_all_smoke(arch_id, phase):
    """Every zoo smoke config, both phases: engine and reference runs
    with one seed must produce bit-identical NetworkResults."""
    desc = describe(f"{arch_id}:{phase}")
    c = cfg()
    a = optimize_network(desc.layers, desc.edges, small_arch(), c)
    b = optimize_network_reference(desc.layers, desc.edges, small_arch(), c)
    assert_results_identical(a, b)


@pytest.mark.parametrize("scenario", ["deepseek_v2_smoke:decode@16x2",
                                      "deepseek_v2_smoke:prefill@32x2",
                                      "deepseek_v2_smoke_ep2:decode@16x2"])
def test_engine_matches_reference_mla(scenario):
    """MLA's head folds at two head dims, the leading dense block and an
    expert share: engine and reference agree bit for bit."""
    desc = describe(scenario)
    c = cfg()
    a = optimize_network(desc.layers, desc.edges, small_arch(), c)
    b = optimize_network_reference(desc.layers, desc.edges, small_arch(), c)
    assert_results_identical(a, b)


@pytest.mark.parametrize("scenario", ["nemotron_3_nano_smoke:decode@16x7",
                                      "nemotron_3_nano_smoke:prefill@16x7",
                                      "nemotron_3_nano_smoke_ep4:decode@16x7",
                                      "nemotron_3_nano_smoke_ep2:prefill@8x3"])
def test_engine_matches_reference_layer_pattern(scenario):
    """A layer pattern's Mamba-2, attention-free MoE and MLP-free
    attention layers, the combine read through FullMap beside the shared
    expert's identity, and an expert share: engine and reference agree
    bit for bit."""
    desc = describe(scenario)
    c = cfg()
    a = optimize_network(desc.layers, desc.edges, small_arch(), c)
    b = optimize_network_reference(desc.layers, desc.edges, small_arch(), c)
    assert_results_identical(a, b)


@pytest.mark.parametrize("objective", OBJECTIVES)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize(
    "scenario", ["deepseek_moe_16b_smoke:decode@16",
                 "mamba2_780m_smoke:prefill@32",
                 "granite_moe_1b_a400m_smoke:decode@16"])
def test_engine_matches_reference_modes_objectives(scenario, mode,
                                                   objective):
    """MoE fan-out and SSD topologies under every (mode, objective):
    the equivalence contract must survive FullMap edges and batched
    matmuls on every search configuration, not just the default."""
    desc = describe(scenario)
    c = cfg(mode=mode, objective=objective)
    a = optimize_network(desc.layers, desc.edges, small_arch(), c)
    b = optimize_network_reference(desc.layers, desc.edges, small_arch(), c)
    assert_results_identical(a, b)


# ---------------------------------------------------------------------------
# Golden MAC accounting (analytic formulas, derived independently)
# ---------------------------------------------------------------------------

_FAC = {"swiglu": 3, "gelu": 2, "relu2": 2}


def _ffn_macs(c, tokens):
    return _FAC[c.mlp] * tokens * c.d_model * c.d_ff


def _attn_macs(c, q, kv, kv_proj_tokens):
    """q/k/v/out projections + the two head-batched score matmuls.
    ``kv_proj_tokens`` is how many tokens the K/V projections process
    (1 in decode — the cache predates the step; enc_frames in cross)."""
    h, kvh, hd = c.n_heads, max(c.n_kv_heads, 1), c.hd
    return (q * c.d_model * h * hd
            + 2 * kv_proj_tokens * c.d_model * kvh * hd
            + 2 * h * q * kv * hd
            + q * h * hd * c.d_model)


def _mla_macs(c, q, kv):
    """MLA (arXiv:2405.04434 Sec. 2.1, Appendix C) over ``q`` query
    tokens and ``kv`` keys. The projections W^DQ, W^UQ, W^QR, W^DKV, W^KR
    and W^O act on the ``q`` new tokens. Prefill decompresses: W^UK and
    W^UV act on every token, and each head's scores and weighted sum run
    over [q^C; q^R] of width nope + rope and v^C of width v. Decode over
    a cache absorbs W^UK into the query (H nope x lat per token) and W^UV
    into the output (H lat x v), so the scores and the weighted sum run
    over the cached latent (width lat) and the shared rope key."""
    h, r, lat = c.n_heads, c.qk_rope_head_dim, c.kv_lora_rank
    nope, v, d, dq = c.qk_nope_head_dim, c.v_head_dim, c.d_model, \
        c.q_lora_rank
    proj = q * (d * dq + dq * h * (nope + r) + d * lat + d * r
                + h * v * d)
    if q == 1 and kv > 1:
        return (proj + h * nope * lat + h * kv * (lat + r)
                + h * kv * lat + h * lat * v)
    return (proj + kv * lat * h * (nope + v)
            + h * q * kv * (nope + r) + h * q * kv * v)


def _self_attn_macs(c, q, kv):
    if c.kv_lora_rank:
        return _mla_macs(c, q, kv)
    return _attn_macs(c, q, kv, q if q == kv else 1)


def _moe_ffn_macs(c, q, shares=1):
    """Router, shared experts at their own width (``d_ff_shared``, else
    ``d_ff``) and this share's routed experts at capacity."""
    cap = max(1, math.ceil(q / max(c.moe_shards, 1) * c.top_k
                           / c.n_experts * c.capacity_factor))
    return (q * c.d_model * c.n_experts
            + c.n_shared_experts * _FAC[c.mlp] * q * c.d_model
            * (c.d_ff_shared or c.d_ff)
            + c.n_experts // shares * _FAC[c.mlp] * cap * c.d_model
            * c.d_ff)


def _moe_macs(c, q, kv, shares=1):
    return _self_attn_macs(c, q, kv) + _moe_ffn_macs(c, q, shares)


def _ssd_macs(c, phase, tokens):
    """Mamba-2: inner width ``mamba_num_heads x head dim`` where the
    config fixes the head count, else ``expand x d_model``."""
    d, p = c.d_model, c.ssm_head_dim
    di = c.ssm_n_heads * p if c.ssm_n_heads else c.ssm_expand * d
    h, g, n = di // p, c.ssm_groups, c.ssm_state
    proj = tokens * d * (2 * di + 2 * g * n + h)
    if phase == "prefill":
        ck = min(c.ssm_chunk, tokens)
        nc = math.ceil(tokens / ck)
        dual = nc * h * (ck * n * ck + ck * ck * p
                         + n * ck * p + ck * n * p)
        return proj + dual + tokens * di * d
    return proj + 2 * h * n * p + di * d


def _audio_macs(c, phase, length, blocks):
    f = c.enc_frames
    h, hd = c.n_heads, c.hd
    if phase == "prefill":
        stem = c.d_model * 80 * (2 * f) * 3 + c.d_model ** 2 * f * 3
        enc = _attn_macs(c, f, f, f) + _ffn_macs(c, f)
        s = length
        cross = _attn_macs(c, s, f, f)
        dec = _attn_macs(c, s, s, s) + cross + _ffn_macs(c, s)
        return stem + enc + blocks * dec
    # decode: cached cross K/V -> only q/qk/av/out on the cross path
    cross = (c.d_model * h * hd + 2 * h * f * hd
             + h * hd * c.d_model)
    dec = _attn_macs(c, 1, length, 1) + cross + _ffn_macs(c, 1)
    return blocks * dec


def analytic_macs(c, phase, length, blocks=1, shares=1):
    """Independent per-model MAC count of ``lower(c, phase, ...)``; with
    ``shares`` the MoE blocks hold one of that many routed-expert
    shares."""
    fam = c.family
    if fam == "audio":
        return _audio_macs(c, phase, length, blocks)
    extra = 0
    if fam == "vlm" and phase == "prefill":
        gh = math.isqrt(c.img_tokens)
        gh, gw = (gh, gh) if gh * gh == c.img_tokens \
            else (c.img_tokens, 1)
        extra = (c.d_model * 3 * gh * gw * 14 * 14
                 + c.img_tokens * c.d_model ** 2)
        length = length + c.img_tokens
    q, kv = (length, length) if phase == "prefill" else (1, length)
    if c.layer_pattern:
        per = {"M": _ssd_macs(c, phase, q), "E": _moe_ffn_macs(c, q, shares),
               "*": _self_attn_macs(c, q, kv)}
        return sum(per[k] for k in c.layer_pattern[:blocks])
    if fam == "moe":
        dense = min(blocks, c.n_dense_layers)
        return (dense * (_self_attn_macs(c, q, kv)
                         + _FAC[c.mlp] * q * c.d_model * c.d_ff_dense)
                + (blocks - dense) * _moe_macs(c, q, kv, shares))
    elif fam == "ssm":
        block = _ssd_macs(c, phase, q)
    elif fam == "hybrid":
        block = (_ssd_macs(c, phase, q)
                 + _attn_macs(c, q, kv, q if phase == "prefill" else 1)
                 + _ffn_macs(c, q))
    else:  # dense, vlm
        block = (_attn_macs(c, q, kv, q if phase == "prefill" else 1)
                 + _ffn_macs(c, q))
    return extra + blocks * block


@pytest.mark.parametrize("smoke", [True, False], ids=["smoke", "full"])
@pytest.mark.parametrize("phase", ["prefill", "decode"])
@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_golden_mac_accounting(arch_id, phase, smoke):
    """sum(l.macs) over a lowered block == the analytic count."""
    c = get_config(arch_id, smoke=smoke)
    length = (64 if smoke else 512) if phase == "prefill" \
        else (16 if smoke else 256)
    layers, _ = lower(c, phase, seq=length, kv_len=length)
    assert sum(l.macs for l in layers) == analytic_macs(c, phase, length)


@pytest.mark.parametrize("arch_id", ["deepseek_moe_16b", "zamba2_1_2b",
                                     "whisper_base", "llava_next_34b",
                                     "deepseek_v2", "nemotron_3_nano"])
def test_golden_macs_multi_block(arch_id):
    """blocks=N scales the repeating tranche only — frontends (vision
    patch-embed, whisper stem+encoder) are lowered once."""
    c = get_config(arch_id, smoke=True)
    layers, _ = lower(c, "prefill", seq=32, blocks=3)
    assert sum(l.macs for l in layers) == analytic_macs(c, "prefill", 32,
                                                        blocks=3)


@pytest.mark.parametrize("phase,length", [("decode", 32768),
                                          ("prefill", 512)])
def test_golden_macs_deepseek_v2_expert_share(phase, length):
    """The benchmark's shape: a dense block and four MoE blocks holding
    one of eight routed-expert shares, at the published widths."""
    c = get_config("deepseek_v2")
    layers, _ = lower(c, phase, seq=length, kv_len=length, blocks=5,
                      share=(0, 8))
    assert sum(l.macs for l in layers) == \
        analytic_macs(c, phase, length, blocks=5, shares=8)
    if phase == "decode":
        assert len(layers) == 14 + 4 * 78


@pytest.mark.parametrize("phase,length", [("decode", 32768),
                                          ("prefill", 512)])
def test_golden_macs_nemotron_period_expert_share(phase, length):
    """The benchmark's shape: the pattern's first period MEMEM*E holding
    one of sixteen routed-expert shares, at the published widths."""
    c = get_config("nemotron_3_nano")
    layers, edges = lower(c, phase, seq=length, kv_len=length, blocks=7,
                          share=(0, 16))
    assert sum(l.macs for l in layers) == \
        analytic_macs(c, phase, length, blocks=7, shares=16)
    kinds = {type(e.cmap).__name__ for es in edges for e in es}
    assert kinds <= {"IdentityMap", "HeadFoldMap", "HeadUnfoldMap",
                     "WeightMap", "FullMap"}
    if phase == "decode":
        # by hand: a Mamba-2 layer 2688 x (2*4096 + 2*8*128 + 64) + 2 x
        # 64*128*64 + 4096 x 2688; an MoE layer 2688 x 128 + 2 x 2688 x
        # 3712 + 8 x 2 x 2688 x 1856; attention 2688 x (4096 + 2*256) +
        # 2 x 32*32768*128 + 4096 x 2688
        assert (39_755_776, 100_122_624, 291_831_808) == (
            analytic_macs(c, phase, length, blocks=1),
            analytic_macs(c, phase, length, blocks=2, shares=16)
            - 39_755_776,
            _self_attn_macs(c, 1, length))
        assert len(layers) == 3 * 8 + 3 * 19 + 6 == 87
        assert sum(l.macs for l in layers) == 711_467_008


#: sha256 of the lowered layers and edges of the benchmark's MoE
#: scenarios, frozen before the MoE feed-forward was split from its
#: attention (``_moe_ffn``): the split must leave them field for field
_PINNED_NETWORKS = {
    "granite_moe_1b_a400m:decode@4096x2":
        (206, "4fc9fb0b61ac85aad903cdf768a4de63"
              "7691799fd99595055ff4737fd8de0101"),
    "deepseek_v2_ep8:decode@32768x5":
        (326, "7e81ed29df92951a34ac5011f3a5c067"
              "82d60dca3e93be7faeb6ec68756d25e5"),
}


@pytest.mark.parametrize("scenario", sorted(_PINNED_NETWORKS))
def test_benchmark_moe_networks_are_pinned(scenario):
    desc = describe(scenario)
    body = {"layers": [dataclasses.asdict(l) for l in desc.layers],
            "edges": [[[e.producer, list(e.cmap.key())] for e in es]
                      for es in desc.edges]}
    digest = hashlib.sha256(json.dumps(body, sort_keys=True)
                            .encode()).hexdigest()
    assert (len(desc.layers), digest) == _PINNED_NETWORKS[scenario]


def test_moe_capacity_formula():
    c = get_config("deepseek_moe_16b")
    cap = moe_capacity(c, 2048)
    assert cap == math.ceil(2048 / c.moe_shards * c.top_k
                            / c.n_experts * c.capacity_factor)
    assert moe_capacity(c, 1) == 1  # floor: never zero slots


# ---------------------------------------------------------------------------
# Lowering invariants (property tests)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(arch_idx=st.integers(0, len(ARCH_IDS) - 1),
       phase=st.sampled_from(["prefill", "decode"]),
       length=st.integers(1, 96),
       blocks=st.integers(1, 3))
def test_property_edges_backward(arch_idx, phase, length, blocks):
    """Every edge points at an already-built layer (DAG by
    construction), for any shape in the supported range."""
    c = get_config(ARCH_IDS[arch_idx], smoke=True)
    layers, edges = lower(c, phase, seq=length, kv_len=length,
                          blocks=blocks)
    assert len(layers) == len(edges)
    for i, deps in enumerate(edges):
        for e in deps:
            assert 0 <= e.producer < i


@settings(max_examples=10, deadline=None)
@given(arch_idx=st.integers(0, len(ARCH_IDS) - 1),
       kv_len=st.integers(1, 64))
def test_property_decode_independent_of_seq(arch_idx, kv_len):
    """Decode lowers one step against the KV length; the prefill
    ``seq`` argument must be entirely inert."""
    c = get_config(ARCH_IDS[arch_idx], smoke=True)
    a_layers, a_edges = lower(c, "decode", seq=7, kv_len=kv_len)
    b_layers, b_edges = lower(c, "decode", seq=4096, kv_len=kv_len)
    assert a_layers == b_layers
    assert [[(e.producer, e.cmap.key()) for e in deps]
            for deps in a_edges] == \
        [[(e.producer, e.cmap.key()) for e in deps] for deps in b_edges]


@pytest.mark.parametrize("arch_id", SMOKE_ARCHS)
def test_no_pool_inference_on_matmul_chains(arch_id):
    """The lowering constructs every IdentityMap explicitly with
    pool=1; matmul-only chains must never pick up an inferred pooling
    factor (that is a conv-chain heuristic)."""
    for phase in ("prefill", "decode"):
        desc = describe(f"{arch_id}:{phase}")
        for deps in desc.edges:
            for e in deps:
                if isinstance(e.cmap, IdentityMap):
                    assert e.cmap.pool == 1


@pytest.mark.parametrize("cmap_kind", ["full", "grouped_weight"])
@pytest.mark.parametrize("seed", range(4))
def test_new_maps_analytical_equals_exhaustive(cmap_kind, seed):
    """C2 oracle for the maps this layer introduced: the analytical
    ready-step analysis must agree with OverlaPIM's exhaustive
    traversal under FullMap and grouped WeightMap edges."""
    rng = random.Random(seed)
    q_len, hd, group = 4, 4, 2
    h = 4  # heads; kv heads = h // group
    # shapes as the lowering builds them: k_proj emits q_len rows of
    # (h//group)*hd columns; qk consumes them as its stationary operand
    lp = matmul("kproj", q_len, 8, (h // group) * hd)
    lc = matmul("qk", q_len, hd, q_len, batch=h)
    arch = dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=8)
    mp = random_mapping(lp, arch, rng, max_steps=256)
    mc = random_mapping(lc, arch, rng, max_steps=256)
    cmap = FullMap() if cmap_kind == "full" else \
        WeightMap(q_len, hd, "qk_weight", group)
    sa, ra = ready_steps_analytical(mp, mc, cmap)
    se, re = ready_steps_exhaustive(mp, mc, cmap)
    assert np.array_equal(ra, re)
    assert np.array_equal(sa[~ra], se[~ra])


def test_weightmap_group_in_key():
    """Grouped maps must not collide with ungrouped ones in engine
    caches (the key IS the cache identity)."""
    assert WeightMap(8, 4, "qk_weight", 1).key() != \
        WeightMap(8, 4, "qk_weight", 4).key()
    assert FullMap().key() == ("full",)


# ---------------------------------------------------------------------------
# Scenario grammar + describe kwargs contract
# ---------------------------------------------------------------------------

def test_scenario_roundtrip_and_defaults():
    sc = parse_scenario("deepseek_moe_16b:prefill@2048")
    assert sc.name == "deepseek_moe_16b:prefill@2048"
    for name in ("deepseek_v2_ep8:decode@32768x5",
                 "deepseek_v2_smoke_ep2:prefill@32x2",
                 "granite_moe_1b_a400m_ep4:decode@4096"):
        assert parse_scenario(name).name == name
    assert parse_scenario("deepseek-v2-smoke-ep2:decode").ep == 2
    assert parse_scenario("deepseek_v2_ep1:decode@16").name == \
        "deepseek_v2:decode@16"
    assert describe("deepseek_v2_ep8:decode@32768x5").name == \
        "deepseek_v2_ep8:decode@32768x5"
    assert parse_scenario("mamba2_780m").phase == "prefill"
    assert parse_scenario("mamba2_780m_smoke:decode").length == 16
    assert parse_scenario("granite-8b-smoke:prefill@64x2").blocks == 2


def test_scenario_layer_pattern_blocks():
    """A patterned config counts blocks in pattern layers; without xN
    it lowers the shortest prefix holding every mixer kind."""
    sc = parse_scenario("nemotron_3_nano:decode@32768")
    assert sc.blocks == 6 and sc.name == "nemotron_3_nano:decode@32768"
    for name in ("nemotron_3_nano_ep16:decode@32768x7",
                 "nemotron_3_nano_smoke:prefill@64x1",
                 "nemotron_3_nano:decode@32768x52"):
        assert parse_scenario(name).name == name
    layers = describe("nemotron_3_nano_smoke:decode@16").layers
    assert {l.name.split(".")[0] for l in layers} == \
        {f"b{i}" for i in range(6)}
    assert layers[-1].name == "b5.out_proj"       # MEMEM*: ends on *
    assert describe("nemotron_3_nano_smoke:decode@16x1").layers[0].name \
        == "z_proj"
    with pytest.raises(ValueError):
        parse_scenario("nemotron_3_nano_smoke:decode@16x8")
    with pytest.raises(ValueError):
        lower(get_config("nemotron_3_nano"), "decode", blocks=53)
    bad = get_config("nemotron_3_nano", smoke=True).with_(
        layer_pattern="ME-")
    with pytest.raises(ValueError, match="name no mixer"):
        lower(bad, "decode", blocks=2)


def test_scenario_errors():
    with pytest.raises(KeyError):
        parse_scenario("not_a_model:prefill")
    with pytest.raises(ValueError):
        parse_scenario("olmo_1b:training")
    with pytest.raises(ValueError):
        parse_scenario("olmo_1b:prefill@0")
    with pytest.raises(ValueError):          # 160 experts, 7 shares
        parse_scenario("deepseek_v2_ep7:decode")
    with pytest.raises(ValueError):          # no routed experts
        parse_scenario("olmo_1b_ep2:decode")


def test_describe_rejects_kwargs_on_fixed_networks():
    """describe('resnet18', seq=99) used to silently ignore the kwarg
    and hand back the stock network — now it must raise."""
    with pytest.raises(TypeError):
        describe("resnet18", seq=99)
    with pytest.raises(TypeError):
        describe("vgg16", heads=4)


def test_describe_scenario_kwargs():
    d = describe("olmo_1b_smoke:prefill", seq=32)
    assert "@32" in d.name
    assert any(l.P == 32 for l in d.layers)
    with pytest.raises(TypeError):
        describe("olmo_1b_smoke:prefill", bogus=1)
    # bert keeps its existing kwargs contract
    d = describe("bert_encoder", seq=64, heads=4, d_model=64, d_ff=128)
    assert len(d.layers) == 8


def test_describe_unknown_network():
    with pytest.raises(KeyError):
        describe("definitely_not_a_network")
