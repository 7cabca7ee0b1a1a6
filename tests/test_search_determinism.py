"""Search-strategy determinism: same ``SearchConfig.seed`` => identical
``NetworkResult``, for every strategy — and every objective — on both
the engine and reference paths.

Candidate generation is the only stochastic element of the search
(``candidates`` seeds a fresh ``random.Random`` per layer from
``cfg.seed``), so repeated runs — including runs on fresh engines, or
interleaved with searches under other seeds/archs — must reproduce the
chosen mappings and every schedule number bit-for-bit. The DSE journal's
resume contract (``repro.dse.persist``) assumes exactly this. The
energy-aware objectives (DESIGN.md Section 9) extend the engine's
equivalence contract: for every (strategy, mode, objective) the engine
must match the reference path on every latency AND energy number.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (LayerSpec, SearchConfig, chain_edges, dram_pim,
                        optimize_network)
from repro.core.engine import OverlapEngine, optimize_network_engine
from repro.core.search import (MODES, OBJECTIVES, STRATEGIES,
                               optimize_network_reference)

ENERGY_OBJECTIVES = tuple(o for o in OBJECTIVES if o != "latency")


def small_arch():
    return dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=64)


def conv_chain():
    return [
        LayerSpec("l0", K=8, C=4, P=8, Q=8, R=3, S=3, pad=1),
        LayerSpec("l1", K=8, C=8, P=8, Q=8, R=3, S=3, pad=1),
        LayerSpec("l2", K=16, C=8, P=4, Q=4, R=3, S=3, stride=2, pad=1),
        LayerSpec("l3", K=16, C=16, P=4, Q=4, R=3, S=3, pad=1),
    ]


def cfg(**kw):
    base = dict(n_candidates=8, seed=11, max_steps=512, mode="transform")
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


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_engine_path_deterministic(strategy):
    """Two engine runs (fresh engines) with one seed are bit-identical."""
    net, arch = conv_chain(), small_arch()
    edges = chain_edges(net)
    c = cfg(strategy=strategy)
    a = optimize_network_engine(net, edges, arch, c,
                                engine=OverlapEngine())
    b = optimize_network_engine(net, edges, arch, c,
                                engine=OverlapEngine())
    assert_results_identical(a, b)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_reference_path_deterministic(strategy):
    net, arch = conv_chain(), small_arch()
    edges = chain_edges(net)
    c = cfg(strategy=strategy)
    a = optimize_network_reference(net, edges, arch, c)
    b = optimize_network_reference(net, edges, arch, c)
    assert_results_identical(a, b)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_engine_matches_reference_per_strategy(strategy):
    """Determinism must hold *across* the two paths too (the engine's
    equivalence contract restated at NetworkResult granularity)."""
    net, arch = conv_chain(), small_arch()
    edges = chain_edges(net)
    c = cfg(strategy=strategy)
    a = optimize_network(net, edges, arch, c)
    b = optimize_network_reference(net, edges, arch, c)
    assert_results_identical(a, b)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_deterministic_under_interleaving(strategy):
    """A shared engine serving other seeds and other archs in between
    must not perturb a re-run (cache reuse is bit-exact, and candidate
    RNG state is per-call)."""
    net, arch = conv_chain(), small_arch()
    edges = chain_edges(net)
    c = cfg(strategy=strategy)
    eng = OverlapEngine()
    a = optimize_network_engine(net, edges, arch, c, engine=eng)
    # interleave: different seed, then a different architecture
    optimize_network_engine(net, edges, arch, cfg(seed=99, strategy=strategy),
                            engine=eng)
    other = dataclasses.replace(arch, word_bits=8)
    optimize_network_engine(net, edges, other, c, engine=eng)
    b = optimize_network_engine(net, edges, arch, c, engine=eng)
    assert_results_identical(a, b)


@pytest.mark.parametrize("objective", ENERGY_OBJECTIVES)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_engine_matches_reference_per_objective(strategy, mode, objective):
    """The engine's equivalence contract extended to the energy-aware
    objectives: all four strategies x all three modes x each new
    objective must produce identical NetworkResults (latency AND energy
    numbers) under the engine and reference paths at the same seed."""
    net, arch = conv_chain(), small_arch()
    edges = chain_edges(net)
    c = cfg(strategy=strategy, mode=mode, objective=objective)
    a = optimize_network(net, edges, arch, c)
    b = optimize_network_reference(net, edges, arch, c)
    assert_results_identical(a, b)


@pytest.mark.parametrize("objective", OBJECTIVES)
def test_engine_matches_reference_objective_refine(objective):
    """The refine loop compares whole-network objective values; engine
    (incremental re-evaluation) and reference must still agree for every
    objective."""
    net, arch = conv_chain(), small_arch()
    edges = chain_edges(net)
    c = cfg(mode="transform", objective=objective, refine_passes=1,
            refine_candidates=4)
    a = optimize_network(net, edges, arch, c)
    b = optimize_network_reference(net, edges, arch, c)
    assert_results_identical(a, b)


@pytest.mark.parametrize("objective", ENERGY_OBJECTIVES)
def test_objective_deterministic_under_interleaving(objective):
    """A shared engine serving other objectives in between must not
    perturb a re-run: score caches are objective-keyed."""
    net, arch = conv_chain(), small_arch()
    edges = chain_edges(net)
    c = cfg(objective=objective)
    eng = OverlapEngine()
    a = optimize_network_engine(net, edges, arch, c, engine=eng)
    for other in OBJECTIVES:
        if other != objective:
            optimize_network_engine(net, edges, arch, cfg(objective=other),
                                    engine=eng)
    b = optimize_network_engine(net, edges, arch, c, engine=eng)
    assert_results_identical(a, b)


def test_objective_stamped_on_result():
    net, arch = conv_chain(), small_arch()
    edges = chain_edges(net)
    for objective in OBJECTIVES:
        r = optimize_network(net, edges, arch, cfg(objective=objective))
        assert r.objective == objective
        assert r.summary()["objective"] == objective


def test_seed_actually_matters():
    """Different seeds explore different candidate pools (sanity check
    that the determinism tests are not vacuous)."""
    net, arch = conv_chain(), small_arch()
    edges = chain_edges(net)
    a = optimize_network(net, edges, arch, cfg(seed=11))
    b = optimize_network(net, edges, arch, cfg(seed=12))
    blocks_a = [l.mapping.blocks for l in a.layers]
    blocks_b = [l.mapping.blocks for l in b.layers]
    assert blocks_a != blocks_b
