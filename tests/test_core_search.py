"""Whole-network search: modes, strategies, chain evaluation, BERT edges."""
import numpy as np
import pytest

from repro.core import (LayerSpec, SearchConfig, chain_edges, describe,
                        dram_pim, evaluate_chain, heuristic_mapping,
                        optimize_network, optimize_network_reference,
                        reram_pim)


def tiny_arch():
    return dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=64)


def tiny_net():
    return [
        LayerSpec("l0", K=8, C=4, P=8, Q=8, R=3, S=3, pad=1),
        LayerSpec("l1", K=8, C=8, P=8, Q=8, R=3, S=3, pad=1),
        LayerSpec("l2", K=16, C=8, P=4, Q=4, R=3, S=3, stride=2, pad=1),
    ]


def cfg(**kw):
    base = dict(n_candidates=12, seed=0, max_steps=512)
    base.update(kw)
    return SearchConfig(**base)


@pytest.mark.parametrize("mode", ["original", "overlap", "transform"])
def test_modes_run_and_order(mode):
    net = tiny_net()
    res = optimize_network(net, chain_edges(net), tiny_arch(),
                           cfg(mode=mode))
    assert res.total_ns > 0
    assert len(res.layers) == 3


def test_overlap_beats_original_on_fixed_mappings():
    """Same mappings evaluated with overlap must never be slower than
    sequential (the motivation experiment, Fig 4)."""
    net = tiny_net()
    arch = tiny_arch()
    maps = [heuristic_mapping(l, arch, 512) for l in net]
    seq = evaluate_chain(maps, chain_edges(net), "original")
    ovl = evaluate_chain(maps, chain_edges(net), "overlap")
    assert ovl.total_ns <= seq.total_ns + 1e-6


def test_search_modes_ordering():
    """Searching with overlap/transform objective should find mappings at
    least as good (in overlapped latency) as evaluating the sequential-best
    mappings with overlap (paper Fig 10 trend)."""
    net = tiny_net()
    arch = tiny_arch()
    edges = chain_edges(net)
    res_orig = optimize_network(net, edges, arch, cfg(mode="original"))
    best_orig_maps = [lr.mapping for lr in res_orig.layers]
    best_orig_overlap = evaluate_chain(best_orig_maps, edges, "overlap")
    res_transform = optimize_network(net, edges, arch,
                                     cfg(mode="transform"))
    assert res_transform.total_ns <= best_orig_overlap.total_ns * 1.05


@pytest.mark.parametrize("strategy",
                         ["forward", "backward", "middle_output",
                          "middle_overall"])
def test_strategies_run(strategy):
    net = tiny_net()
    res = optimize_network(net, chain_edges(net), tiny_arch(),
                           cfg(mode="transform", strategy=strategy))
    assert res.total_ns > 0


def test_reram_arch_runs():
    net = tiny_net()
    arch = reram_pim(tiles_per_layer=2, blocks_per_tile=2,
                     columns_per_block=64)
    res = optimize_network(net, chain_edges(net), arch, cfg())
    assert res.total_ns > 0


def test_bert_edges_and_search():
    desc = describe("bert_encoder", seq=16, d_model=8, heads=2, d_ff=16)
    assert len(desc.layers) == 8
    # qk depends on q(0) and k(1); av on qk(3) and v(2)
    assert {e.producer for e in desc.edges[3]} == {0, 1}
    assert {e.producer for e in desc.edges[4]} == {3, 2}
    res = optimize_network(desc.layers, desc.edges, tiny_arch(),
                           cfg(mode="transform"))
    assert res.total_ns > 0


def test_deterministic_given_seed():
    net = tiny_net()
    a = optimize_network(net, chain_edges(net), tiny_arch(), cfg())
    b = optimize_network(net, chain_edges(net), tiny_arch(), cfg())
    assert a.total_ns == b.total_ns


def test_chain_monotone_finish_times():
    net = tiny_net()
    arch = tiny_arch()
    maps = [heuristic_mapping(l, arch, 512) for l in net]
    res = evaluate_chain(maps, chain_edges(net), "overlap")
    for lr in res.layers:
        # finish times strictly increase along each bank's steps
        assert np.all(np.diff(lr.finish_ns, axis=1) > 0)


def test_refinement_never_worse():
    """Beyond-paper coordinate-descent refinement only accepts strict
    improvements of the whole-network objective."""
    net = tiny_net()
    base = optimize_network(net, chain_edges(net), tiny_arch(),
                            cfg(mode="transform"))
    ref = optimize_network(net, chain_edges(net), tiny_arch(),
                           cfg(mode="transform", refine_passes=1))
    assert ref.total_ns <= base.total_ns + 1e-6


@pytest.mark.parametrize("mode", ["overlap", "transform"])
def test_reference_exhaustive_changes_code_path(monkeypatch, mode):
    """``optimize_network_reference(exhaustive=True)`` routes the
    ready-step analysis through OverlaPIM's exhaustive traversal (it was
    once declared but never consulted — baseline comparisons silently ran
    the fast path)."""
    import repro.core.search as search_mod

    calls = {"exh": 0, "ana": 0}
    real_exh = search_mod.ready_steps_exhaustive
    real_ana = search_mod.ready_steps_analytical

    def count_exh(*a, **kw):
        calls["exh"] += 1
        return real_exh(*a, **kw)

    def count_ana(*a, **kw):
        calls["ana"] += 1
        return real_ana(*a, **kw)

    monkeypatch.setattr(search_mod, "ready_steps_exhaustive", count_exh)
    monkeypatch.setattr(search_mod, "ready_steps_analytical", count_ana)

    net = tiny_net()
    small = cfg(n_candidates=3, max_steps=64, mode=mode)
    on = optimize_network_reference(net, chain_edges(net), tiny_arch(),
                                    small, exhaustive=True)
    assert calls["exh"] > 0 and calls["ana"] == 0

    calls["exh"] = calls["ana"] = 0
    off = optimize_network_reference(net, chain_edges(net), tiny_arch(),
                                     small)
    assert calls["exh"] == 0 and calls["ana"] > 0
    # the exhaustive analysis is the oracle the analytical closed form
    # reproduces, so both pick the same mappings and timings
    assert on.total_ns == off.total_ns
