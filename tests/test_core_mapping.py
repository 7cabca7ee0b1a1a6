"""Unit tests: workloads, architectures, mapping representation."""
import dataclasses
import random

import pytest

from repro.core import (DIMS, REDUCTION_DIMS, ArchSpec, Level, LayerSpec,
                        SearchConfig, describe, dram_pim, get_network,
                        heuristic_mapping, random_mapping, reram_pim,
                        tpu_spatial)
from repro.core.mapping import (Mapping, _assemble_blocks, _prod,
                                _slot_order, divisors)
from repro.core.search import candidates


def small_arch():
    return dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=16)


def small_layer():
    return LayerSpec("l", K=8, C=4, P=12, Q=12, R=3, S=3, pad=1)


def test_networks_shapes():
    r18 = get_network("resnet18")
    assert len(r18) == 20
    assert r18[0].C == 3 and r18[0].stride == 2
    assert len(get_network("vgg16")) == 13
    r50 = get_network("resnet50")
    assert len(r50) == 49
    # chain consistency: consumer C == producer K for conv chains
    for net in ("vgg16",):
        layers = get_network(net)
        for a, b in zip(layers, layers[1:]):
            assert b.C == a.K


def test_layer_derived_quantities():
    l = small_layer()
    assert l.macs == 8 * 4 * 12 * 12 * 9
    assert l.input_shape == (4, 14, 14)
    assert l.output_size() == 12 * 12 * 8
    assert l.overall_size() == 12 * 12 * 4 * 8


def test_divisors():
    assert divisors(12) == (1, 2, 3, 4, 6, 12)
    assert divisors(1) == (1,)
    assert divisors(7) == (1, 7)


def test_heuristic_mapping_valid():
    m = heuristic_mapping(small_layer(), small_arch())
    m.validate()
    assert m.n_banks <= 4
    assert m.n_columns_used <= 16
    # full factorization -> macs conserved
    assert m.macs_per_step() * m.n_steps * m.n_banks == small_layer().macs


@pytest.mark.parametrize("seed", range(8))
def test_random_mapping_valid(seed):
    rng = random.Random(seed)
    layer = small_layer()
    arch = small_arch()
    m = random_mapping(layer, arch, rng, max_steps=4096)
    m.validate()
    assert m.n_steps <= 4096
    assert m.macs_per_step() * m.n_steps * m.n_banks == layer.macs


def test_time_strides_mixed_radix():
    m = heuristic_mapping(small_layer(), small_arch())
    # strides are a proper mixed radix: stride[i] = prod sizes inner to i
    sizes = [lp.size for lp in m.time_loops]
    strides = m.time_strides
    acc = 1
    for sz, st in zip(reversed(sizes), reversed(strides)):
        assert st == acc
        acc *= sz


def test_arch_presets():
    d = dram_pim()
    assert d.n_target_instances == 16
    assert d.columns_per_target == 8192
    assert d.op_latency("add") == 196.0
    assert d.op_latency("mul") == 980.0
    r = reram_pim()
    assert r.op_latency("add") == 442.0
    # AAP fallback model when ops not pinned
    bare = dram_pim()
    object.__setattr__(bare.levels[-1], "pim_ops", None)
    assert bare.op_latency("add") == (4 * 16 + 1) * bare.timing.t_aap


def test_reduction_dims_never_spatial_above_target():
    rng = random.Random(0)
    arch = small_arch()
    for s in range(20):
        m = random_mapping(small_layer(), arch, random.Random(s), 4096)
        for li, lp in m.nest:
            if lp.spatial and lp.dim in ("C", "R", "S"):
                assert li == arch.target_index


# ---------------------------------------------------------------------------
# Stream identity: the draw loop spends the same random calls, in the same
# order, as the per-slot sampler it replaced. The oracle below is that
# sampler, frozen, with ``rng.choice`` for every divisor draw.
# ---------------------------------------------------------------------------

def _oracle_divisor_le(n, cap, rng):
    opts = [d for d in divisors(n) if d <= cap]
    return rng.choice(opts)


def _oracle_random_mapping(layer, arch, rng, max_steps=65536, max_tries=64,
                           stream=None):
    t = arch.target_index
    n_levels = len(arch.levels)
    for _ in range(max_tries):
        per_slot = {s: {} for s in _slot_order(arch)}
        ok = True
        for d in DIMS:
            rem = layer.dim(d)
            for li in range(n_levels - 1):
                cap = arch.levels[li + 1].fanout
                if d in REDUCTION_DIMS and li != t:
                    f = 1
                elif rng.random() < 0.5:
                    f = _oracle_divisor_le(rem, cap, rng)
                else:
                    f = 1
                per_slot[(li, True)][d] = f
                rem //= f
            for li in range(n_levels):
                if li == n_levels - 1:
                    f = rem
                else:
                    f = _oracle_divisor_le(rem, rem, rng)
                per_slot[(li, False)][d] = f
                rem //= f
            if rem != 1:
                ok = False
                break
        if not ok:
            continue
        for li in range(n_levels - 1):
            cap = arch.levels[li + 1].fanout
            sl = per_slot[(li, True)]
            dims_sorted = sorted(sl, key=lambda d: -sl[d])
            while _prod(sl.values()) > cap:
                dd = dims_sorted[0]
                per_slot[(li, False)][dd] *= sl[dd]
                sl[dd] = 1
                dims_sorted = sorted(sl, key=lambda d: -sl[d])
        n_steps = 1
        for li in range(t + 1):
            n_steps *= _prod(per_slot[(li, False)].values())
        if n_steps > max_steps:
            continue
        do_stream = stream if stream is not None else (rng.random() < 0.5)
        blocks = _assemble_blocks(arch, per_slot, rng, stream=do_stream)
        m = Mapping(layer=layer, arch=arch, blocks=blocks)
        try:
            m.validate()
        except ValueError:
            continue
        return m
    return heuristic_mapping(layer, arch)


def _three_level_arch():
    """Target at index 1, a fanout-1 level and a prime fanout."""
    return ArchSpec(name="three", levels=(
        Level("Rank", fanout=1), Level("Bank", fanout=3),
        Level("Column", fanout=64, pim_ops={"add": 1.0, "mul": 1.0})),
        target_level="Bank")


_ARCHS = {
    "dram_pim": dram_pim,
    "reram_pim": reram_pim,
    "small_dram": lambda: dram_pim(channels_per_layer=2, banks_per_channel=2,
                                   columns_per_bank=4),
    "tpu_spatial": tpu_spatial,
    "three_level": _three_level_arch,
}

# size-1 dims, prime dims (7, 13, 97, 127), a 32768-long dim, powers of two
_SHAPES = (
    LayerSpec("mm", K=4096, C=512, P=1, Q=1),
    LayerSpec("kv", K=32768, C=128, P=1, Q=1, N=1),
    LayerSpec("prime", K=97, C=13, P=7, Q=127, R=3, S=1),
    LayerSpec("conv", K=64, C=64, P=56, Q=56, R=3, S=3, pad=1),
    LayerSpec("ones", K=1, C=1, P=1, Q=1),
    LayerSpec("seq", K=32768, C=64, P=32768, Q=1),
)


@pytest.mark.parametrize("arch_name", sorted(_ARCHS))
@pytest.mark.parametrize("max_steps", [256, 2048, 16384])
@pytest.mark.parametrize("stream", [None, True, False])
def test_random_mapping_spends_the_oracle_stream(arch_name, max_steps,
                                                 stream):
    arch = _ARCHS[arch_name]()
    for seed in (0, 1, 2**31 + 5):
        new, old = random.Random(seed), random.Random(seed)
        for layer in _SHAPES:
            for _ in range(3):
                got = random_mapping(layer, arch, new, max_steps,
                                     stream=stream)
                want = _oracle_random_mapping(layer, arch, old, max_steps,
                                              stream=stream)
                assert got.blocks == want.blocks, (layer.name, seed)
                assert new.getstate() == old.getstate(), (layer.name, seed)


def test_random_mapping_fallback_spends_the_oracle_stream():
    """``max_tries`` spent on rejected tries: both fall back to the
    heuristic mapping with the same state."""
    arch = dram_pim()
    layer = LayerSpec("seq", K=32768, C=64, P=32768, Q=1)
    new, old = random.Random(3), random.Random(3)
    got = random_mapping(layer, arch, new, max_steps=1, max_tries=4)
    want = _oracle_random_mapping(layer, arch, old, max_steps=1, max_tries=4)
    assert got.blocks == want.blocks == heuristic_mapping(layer, arch).blocks
    assert new.getstate() == old.getstate()


def _oracle_candidates(layer, arch, cfg, salt):
    rng = random.Random((cfg.seed << 20) ^ salt)
    out = [heuristic_mapping(layer, arch, cfg.max_steps)]
    seen = {out[0].blocks}
    for _ in range(cfg.n_candidates - 1):
        m = _oracle_random_mapping(layer, arch, rng, cfg.max_steps)
        if m.blocks not in seen:
            seen.add(m.blocks)
            out.append(m)
    return out


@pytest.mark.parametrize("network,n_candidates,max_steps", [
    ("resnet18", 48, 16384),
    ("granite_moe_1b_a400m:decode@4096x2", 8, 2048),
    ("deepseek_v2_ep8:decode@32768x5", 8, 2048),
])
def test_candidate_pools_match_the_oracle(network, n_candidates, max_steps):
    """Every distinct layer shape of the benchmarked networks draws the
    pool the frozen sampler draws, for several seeds and salts (refinement
    salts included)."""
    arch = dram_pim()
    shapes = {dataclasses.replace(l, name=""): None
              for l in describe(network).layers}
    for seed in (0, 2**31 + 1):
        cfg = SearchConfig(n_candidates=n_candidates, max_steps=max_steps,
                           seed=seed)
        for i, layer in enumerate(shapes):
            for salt in (i, i + 7919):
                got = candidates(layer, arch, cfg, salt)
                want = _oracle_candidates(layer, arch, cfg, salt)
                assert [m.blocks for m in got] == [m.blocks for m in want]


def test_candidates_counts_draws_and_tries():
    arch = dram_pim()
    layer = LayerSpec("seq", K=32768, C=64, P=32768, Q=1)
    cfg = SearchConfig(n_candidates=8, max_steps=2048, seed=5)
    stats = {"draws": 0, "draw_tries": 0}
    candidates(layer, arch, cfg, 0, stats=stats)
    assert stats["draws"] == 7
    assert stats["draw_tries"] > stats["draws"]   # this shape rejects
