"""Coverage: PIM performance/energy model invariants, network interface
edge wiring (pool inference, residuals), config registry."""
import pytest

from repro.configs import ARCH_IDS, SHAPES, cell_status, cells, get_config
from repro.core import (LayerSpec, analyze, describe, dram_pim,
                        heuristic_mapping, reram_pim, step_latency_ns)
from repro.core.interface import _pool_between


# -- perf model ---------------------------------------------------------------

def small_arch(cols=256):
    return dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=cols)


def test_step_latency_positive_and_scales_with_work():
    l_small = LayerSpec("s", K=8, C=8, P=8, Q=8, R=3, S=3, pad=1)
    l_big = LayerSpec("b", K=16, C=16, P=16, Q=16, R=3, S=3, pad=1)
    m1 = heuristic_mapping(l_small, small_arch(), 4096)
    m2 = heuristic_mapping(l_big, small_arch(), 4096)
    p1, p2 = analyze(m1), analyze(m2)
    assert p1.compute_ns > 0
    assert p2.compute_ns > p1.compute_ns  # 16x the MACs
    # MAC conservation through the decomposition
    assert m1.macs_per_step() * m1.n_steps * m1.n_banks == l_small.macs


def test_more_columns_is_faster():
    l = LayerSpec("l", K=16, C=16, P=16, Q=16, R=3, S=3, pad=1)
    slow = analyze(heuristic_mapping(l, small_arch(64), 4096))
    fast = analyze(heuristic_mapping(l, small_arch(1024), 4096))
    assert fast.compute_ns < slow.compute_ns


def test_energy_accounting():
    l = LayerSpec("l", K=8, C=8, P=8, Q=8, R=3, S=3, pad=1)
    p = analyze(heuristic_mapping(l, small_arch(), 4096))
    # bit-serial MAC energy: (n+1) adds of (4n+1) AAPs each
    arch = small_arch()
    n = arch.word_bits
    per_mac = (n + 1) * (4 * n + 1) * arch.timing.e_act
    assert p.energy_pj >= l.macs * per_mac


def test_reram_latency_constants_differ_from_dram():
    l = LayerSpec("l", K=8, C=8, P=8, Q=8, R=3, S=3, pad=1)
    d = step_latency_ns(heuristic_mapping(l, dram_pim(
        channels_per_layer=2, banks_per_channel=2,
        columns_per_bank=256), 4096))
    r = step_latency_ns(heuristic_mapping(l, reram_pim(
        tiles_per_layer=2, blocks_per_tile=2,
        columns_per_block=256), 4096))
    assert d != r  # 196/980 vs 442/696 op latencies


# -- interface / edges --------------------------------------------------------

def test_pool_inference_vgg():
    layers = describe("vgg16").layers
    # conv2 (224) -> conv3 (112): pool 2 between blocks
    assert _pool_between(layers[1], layers[2]) == 2
    # within a block: no pool
    assert _pool_between(layers[2], layers[3]) == 1


def test_resnet18_residual_edges():
    desc = describe("resnet18")
    by_name = {l.name: i for i, l in enumerate(desc.layers)}
    # the block after an add consumes both main and downsample paths
    i = by_name["s2b1c1"]
    prods = {e.producer for e in desc.edges[i]}
    assert by_name["s2b0c2"] in prods and by_name["s2b0ds"] in prods
    # downsample consumes the stage input, not its neighbor
    ds = by_name["s2b0ds"]
    assert desc.edges[ds][0].producer == by_name["s1b1c2"]
    # edges always point backward (searchable order)
    for i, es in enumerate(desc.edges):
        assert all(e.producer < i for e in es)


def test_stem_pool_resnet():
    layers = describe("resnet18").layers
    assert _pool_between(layers[0], layers[1]) == 2  # maxpool after conv1


# -- config registry ----------------------------------------------------------

def test_all_archs_and_cells_accounted():
    assert len(ARCH_IDS) == 12
    assert len(SHAPES) == 4
    full = cells(include_skipped=True)
    assert len(full) == 48
    live = cells(include_skipped=False)
    assert len(live) == 39  # 9 long_500k skips for full-attention archs
    ok, why = cell_status("nemotron_3_nano", "long_500k")
    assert ok                # hybrid: six attention layers of 52
    ok, why = cell_status("mamba2_780m", "long_500k")
    assert ok
    ok, why = cell_status("granite_8b", "long_500k")
    assert not ok and "sub-quadratic" in why


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_config_fields_match_assignment(arch):
    cfg = get_config(arch)
    expect = {
        "mamba2_780m": (48, 1536, 50280), "zamba2_1_2b": (38, 2048, 32000),
        "granite_moe_1b_a400m": (24, 1024, 49155),
        "deepseek_moe_16b": (28, 2048, 102400),
        "olmo_1b": (16, 2048, 50304), "phi3_mini_3_8b": (32, 3072, 32064),
        "stablelm_3b": (32, 2560, 50304), "granite_8b": (36, 4096, 49152),
        "whisper_base": (6, 512, 51865),
        "llava_next_34b": (60, 7168, 64000),
        "deepseek_v2": (60, 5120, 102400),
        "nemotron_3_nano": (52, 2688, 131072),
    }[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == expect
    smoke = get_config(arch, smoke=True)
    assert smoke.family == cfg.family
    assert smoke.d_model < cfg.d_model


def test_dashed_aliases():
    assert get_config("mamba2-780m").arch_id == "mamba2_780m"
