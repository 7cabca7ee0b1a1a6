"""Batched engine vs per-candidate reference: differential oracle tests.

The engine's contract (DESIGN.md Section 6) is bit-identical results —
same ready/step matrices, same candidate scores, same chosen mappings,
same ``total_ns`` — for every mode and strategy. These tests enforce it
against the pre-engine path kept in ``core.search`` / ``core.overlap``.
"""
import dataclasses
import random

import numpy as np
import pytest

from repro.core import (Edge, FullMap, IdentityMap, LayerSpec,
                        SearchConfig, chain_edges, describe, dram_pim,
                        evaluate_chain, max_step_in_rect, optimize_network,
                        random_mapping, ready_steps_analytical,
                        ready_steps_exhaustive)
from repro.core.engine import (OverlapEngine, max_step_in_rect_dedup,
                               optimize_network_engine)
from repro.core.search import (_consumers_of, _score_backward,
                               _score_forward, candidates,
                               optimize_network_reference)
from repro.core.transform import transform_schedule


def small_arch(cols=64):
    return dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=cols)


def conv_chain():
    return [
        LayerSpec("l0", K=8, C=4, P=8, Q=8, R=3, S=3, pad=1),
        LayerSpec("l1", K=8, C=8, P=8, Q=8, R=3, S=3, pad=1),
        LayerSpec("l2", K=16, C=8, P=4, Q=4, R=3, S=3, stride=2, pad=1),
    ]


def bert_desc():
    return describe("bert_encoder", seq=16, d_model=8, heads=2, d_ff=16)


def cfg(**kw):
    base = dict(n_candidates=10, seed=0, max_steps=512)
    base.update(kw)
    return SearchConfig(**base)


# ---------------------------------------------------------------------------
# Ready-step analysis: engine (dedup / separable / batched) vs reference.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_engine_ready_steps_identity_bit_identical(seed):
    """Separable IdentityMap fast path == ready_steps_analytical, across
    strides, pads and pooling factors."""
    rng = random.Random(seed)
    P = rng.choice([4, 6, 8])
    K1 = rng.choice([2, 4])
    R = rng.choice([1, 3])
    st = rng.choice([1, 2])
    pool = rng.choice([1, 2])
    arch = small_arch(4)
    lp = LayerSpec("p", K=K1, C=2, P=P * st * pool, Q=P * st * pool,
                   R=R, S=R, pad=R // 2)
    lc = LayerSpec("c", K=2, C=K1, P=P, Q=P, R=R, S=R, stride=st,
                   pad=R // 2)
    mp = random_mapping(lp, arch, rng, 256)
    mc = random_mapping(lc, arch, rng, 256)
    cm = IdentityMap(pool=pool)
    sa, ra = ready_steps_analytical(mp, mc, cm)
    se, re = OverlapEngine().ready_steps(mp, mc, cm)
    assert np.array_equal(ra, re)
    assert np.array_equal(sa, se)


@pytest.mark.parametrize("seed", range(4))
def test_engine_ready_steps_bert_edges_bit_identical(seed):
    """Engine ready steps == reference on every BERT edge kind (HeadFold,
    HeadUnfold, both WeightMaps, Identity)."""
    desc = bert_desc()
    arch = small_arch(8)
    rng = random.Random(seed)
    maps = [random_mapping(l, arch, rng, 128) for l in desc.layers]
    eng = OverlapEngine()
    for i, edges in enumerate(desc.edges):
        for e in edges:
            sa, ra = ready_steps_analytical(maps[e.producer], maps[i],
                                            e.cmap)
            se, re = eng.ready_steps(maps[e.producer], maps[i], e.cmap)
            assert np.array_equal(ra, re), (i, e.producer)
            assert np.array_equal(sa, se), (i, e.producer)


def test_engine_ready_steps_batch_matches_single():
    """Batched (stacked) ready steps == per-candidate, over a candidate
    pool, for identity and non-identity maps."""
    desc = bert_desc()
    arch = small_arch(8)
    c = cfg()
    eng = OverlapEngine()
    rng = random.Random(3)
    prod = random_mapping(desc.layers[0], arch, rng, 128)
    for i in (3, 5):  # qk (HeadFold edge from q), out_proj (HeadUnfold)
        pool = candidates(desc.layers[i], arch, c, salt=i)
        for e in desc.edges[i]:
            if e.producer != 0:
                continue
            got = eng.ready_steps_batch(prod, pool, e.cmap)
            for m, (se, re) in zip(pool, got):
                sa, ra = ready_steps_analytical(prod, m, e.cmap)
                assert np.array_equal(sa, se)
                assert np.array_equal(ra, re)


@pytest.mark.parametrize("shape", ["conv", "matmul"])
@pytest.mark.parametrize("seed", range(4))
def test_engine_ready_steps_full_map_bit_identical(seed, shape):
    """FullMap closed form == ready_steps_analytical (step and ready0),
    batched == per-candidate, and both == the exhaustive traversal on the
    valid entries, for consumers whose grid differs from the producer's."""
    rng = random.Random(seed)
    arch = small_arch(4)
    if shape == "conv":
        lp = LayerSpec("p", K=rng.choice([2, 4]), C=2, P=4, Q=4, R=3, S=3,
                       pad=1)
        lc = LayerSpec("c", K=2, C=4, P=2, Q=2, R=3, S=3, stride=2, pad=1)
    else:
        lp = LayerSpec("p", K=rng.choice([4, 8]), C=4, P=4, Q=1)
        lc = LayerSpec("c", K=4, C=2, P=2, Q=1, N=2)
    mp = random_mapping(lp, arch, rng, 32)
    cands = [random_mapping(lc, arch, rng, ms) for ms in (4, 16, 32, 32)]
    assert any((m.n_banks, m.n_steps) != (mp.n_banks, mp.n_steps)
               for m in cands)
    cm = FullMap()
    got = OverlapEngine().ready_steps_batch(mp, cands, cm)
    for mc, (sb, rb) in zip(cands, got):
        sa, ra = ready_steps_analytical(mp, mc, cm)
        se, re = OverlapEngine().ready_steps(mp, mc, cm)
        sx, rx = ready_steps_exhaustive(mp, mc, cm)
        assert se.shape == sa.shape == (mc.n_banks, mc.n_steps)
        assert se.dtype == sa.dtype == np.int64
        assert np.array_equal(ra, re) and np.array_equal(sa, se)
        assert np.array_equal(rb, re) and np.array_equal(sb, se)
        assert not ra.any() and np.array_equal(rx, ra)
        assert np.array_equal(sx[~rx], se[~rx])


@pytest.mark.parametrize("seed", range(4))
def test_max_step_in_rect_dedup_matches(seed):
    """Interval-dedup digit scan == reference scan on random rectangles."""
    rng = random.Random(seed)
    arch = small_arch(4)
    lp = LayerSpec("p", K=4, C=2, P=8, Q=8, R=3, S=3, pad=1)
    mp = random_mapping(lp, arch, rng, 256)
    nrng = np.random.RandomState(seed)
    shape = (3, 17)
    plo, phi = {}, {}
    for d in ("K", "P", "Q"):
        dim = lp.dim(d)
        lo = nrng.randint(0, dim, size=shape)
        ext = nrng.randint(1, dim + 1, size=shape)
        plo[d] = lo
        phi[d] = np.minimum(lo + ext, dim)
    assert np.array_equal(max_step_in_rect(mp, plo, phi),
                          max_step_in_rect_dedup(mp, plo, phi))


# ---------------------------------------------------------------------------
# Candidate scoring: engine == reference, forward and backward.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["original", "overlap", "transform"])
def test_score_forward_batch_matches_reference(mode):
    net = conv_chain()
    edges = chain_edges(net)
    arch = small_arch()
    c = cfg(mode=mode)
    ref = optimize_network_reference(net, edges, arch, c)
    done = {i: lr for i, lr in enumerate(ref.layers)}
    eng = OverlapEngine()
    for i in range(len(net)):
        pool = candidates(net[i], arch, c, salt=i)
        has_cons = bool(_consumers_of(edges, i))
        s_ref = np.array([_score_forward(i, m, edges, done, mode, has_cons)
                          for m in pool])
        s_eng = eng.score_forward_batch(i, pool, edges, done, mode,
                                        has_cons)
        assert np.array_equal(s_ref, s_eng), i


@pytest.mark.parametrize("mode", ["overlap", "transform"])
def test_score_backward_matches_reference(mode):
    net = conv_chain()
    edges = chain_edges(net)
    arch = small_arch()
    c = cfg(mode=mode)
    fixed = {2: candidates(net[2], arch, c, salt=2)[0]}
    eng = OverlapEngine()
    for m in candidates(net[1], arch, c, salt=1):
        assert eng.score_backward(1, m, edges, fixed, mode) \
            == _score_backward(1, m, edges, fixed, mode)


# ---------------------------------------------------------------------------
# Chain evaluation: incremental == full, engine == reference.
# ---------------------------------------------------------------------------

def test_incremental_chain_eval_matches_full():
    desc = bert_desc()
    arch = small_arch()
    c = cfg()
    rng = random.Random(11)
    base_maps = [random_mapping(l, arch, rng, 128) for l in desc.layers]
    eng = OverlapEngine()
    for mode in ("original", "overlap", "transform"):
        base = eng.evaluate_chain(base_maps, desc.edges, mode)
        ref_base = evaluate_chain(base_maps, desc.edges, mode)
        assert base.total_ns == ref_base.total_ns
        for trial_at in range(len(base_maps)):
            trial = list(base_maps)
            trial[trial_at] = random_mapping(desc.layers[trial_at], arch,
                                             rng, 128)
            inc = eng.evaluate_chain(trial, desc.edges, mode,
                                     reuse=(base.layers, base_maps))
            full = evaluate_chain(trial, desc.edges, mode)
            assert inc.total_ns == full.total_ns, (mode, trial_at)
            assert inc.per_layer_ns == pytest.approx(full.per_layer_ns,
                                                     abs=0)


def test_transform_schedule_precomputed_order():
    """transform_schedule(order=...) == transform_schedule() when the order
    equals the stable argsort of the ready times."""
    rng = np.random.RandomState(5)
    ready = rng.choice([0.0, 10.0, 25.0, 70.0], size=(4, 33))
    order = np.argsort(ready.reshape(-1), kind="stable")
    a = transform_schedule(ready, 7.0, 2.5)
    b = transform_schedule(ready, 7.0, 2.5, order=order)
    assert a.end_ns == b.end_ns
    assert np.array_equal(a.finish_ns, b.finish_ns)
    assert a.moved_frac == b.moved_frac


# ---------------------------------------------------------------------------
# Whole-search differential: acceptance criterion — all four strategies on
# vgg16 and bert_encoder, engine == reference (same mappings, same total).
# ---------------------------------------------------------------------------

def _assert_search_equal(layers, edges, arch, c):
    a = optimize_network_engine(layers, edges, arch, c)
    b = optimize_network_reference(layers, edges, arch, c)
    assert a.total_ns == b.total_ns
    assert a.per_layer_ns == pytest.approx(b.per_layer_ns, abs=0)
    for x, y in zip(a.layers, b.layers):
        assert x.mapping.blocks == y.mapping.blocks


@pytest.mark.slow
@pytest.mark.parametrize("strategy",
                         ["forward", "backward", "middle_output",
                          "middle_overall"])
def test_search_differential_vgg16(strategy):
    desc = describe("vgg16")
    arch = dram_pim(channels_per_layer=2)
    _assert_search_equal(desc.layers, desc.edges, arch,
                         cfg(n_candidates=4, max_steps=1024,
                             mode="transform", strategy=strategy))


@pytest.mark.parametrize("strategy",
                         ["forward", "backward", "middle_output",
                          "middle_overall"])
@pytest.mark.parametrize("mode", ["original", "overlap", "transform"])
def test_search_differential_bert(strategy, mode):
    desc = bert_desc()
    _assert_search_equal(desc.layers, desc.edges, small_arch(),
                         cfg(mode=mode, strategy=strategy))


@pytest.mark.parametrize("strategy", ["forward", "middle_output"])
def test_search_differential_with_refinement(strategy):
    """Refine trials reuse committed prefixes — totals must still match the
    reference's full re-evaluation exactly."""
    net = conv_chain()
    _assert_search_equal(net, chain_edges(net), small_arch(),
                         cfg(mode="transform", strategy=strategy,
                             refine_passes=2))


def test_engine_reuse_across_archs_keyed_bundles():
    """A reused engine must not serve cached analysis from a previous
    arch: mapping content keys are arch-agnostic, so caches are bundled
    per ``ArchSpec.to_key()`` (regression test for a cache-staleness bug,
    now also the DSE multi-arch reuse contract)."""
    net = conv_chain()
    edges = chain_edges(net)
    arch_a = small_arch(64)
    arch_b = dataclasses.replace(arch_a, word_bits=8)
    eng = OverlapEngine()
    for arch in (arch_a, arch_b, arch_a):
        c = cfg(mode="transform")
        got = optimize_network_engine(net, edges, arch, c, engine=eng)
        ref = optimize_network_reference(net, edges, arch, c)
        assert got.total_ns == ref.total_ns
        # backward scoring path too (shares the score/ready caches)
        fixed = {2: candidates(net[2], arch, c, salt=2)[0]}
        m = candidates(net[1], arch, c, salt=1)[0]
        assert eng.score_backward(1, m, edges, fixed, "transform") \
            == _score_backward(1, m, edges, fixed, "transform")
    # two distinct archs -> two bundles, revisits resume the existing one
    assert eng.n_arch_bundles == 2


def test_engine_evict_arch():
    """Evicting a bundle frees it without breaking later searches; a
    fresh search under the evicted arch rebuilds from scratch and still
    matches the reference."""
    net = conv_chain()
    edges = chain_edges(net)
    arch_a = small_arch(64)
    arch_b = dataclasses.replace(arch_a, word_bits=8)
    eng = OverlapEngine()
    c = cfg(mode="transform")
    optimize_network_engine(net, edges, arch_a, c, engine=eng)
    optimize_network_engine(net, edges, arch_b, c, engine=eng)
    assert eng.n_arch_bundles == 2
    assert eng.evict_arch(arch_b)          # current bundle: resets cleanly
    assert not eng.evict_arch(arch_b)      # already gone
    assert eng.evict_arch(arch_a.to_key()) # by key string
    assert eng.n_arch_bundles == 0
    got = optimize_network_engine(net, edges, arch_b, c, engine=eng)
    ref = optimize_network_reference(net, edges, arch_b, c)
    assert got.total_ns == ref.total_ns


def test_evict_arch_does_not_clobber_other_bundles():
    """Evicting the current arch must not make the next arch switch
    overwrite a different arch's warm bundle (regression: the post-evict
    state once registered its fresh bundle under the revisited key)."""
    net = conv_chain()
    edges = chain_edges(net)
    arch_a = small_arch(64)
    arch_b = dataclasses.replace(arch_a, word_bits=8)
    eng = OverlapEngine()
    c = cfg(mode="transform")
    optimize_network_engine(net, edges, arch_a, c, engine=eng)
    optimize_network_engine(net, edges, arch_b, c, engine=eng)
    bundle_b = eng._bundles[arch_b.to_key()]
    n_ready_b = len(bundle_b.ready)
    assert n_ready_b > 0
    optimize_network_engine(net, edges, arch_a, c, engine=eng)
    eng.evict_arch(arch_a)
    got = optimize_network_engine(net, edges, arch_b, c, engine=eng)
    assert eng._bundles[arch_b.to_key()] is bundle_b
    assert len(bundle_b.ready) == n_ready_b  # warm, not recomputed
    ref = optimize_network_reference(net, edges, arch_b, c)
    assert got.total_ns == ref.total_ns


def test_engine_multi_arch_bundle_retention():
    """Returning to a previously seen architecture — via a content-equal
    but distinct ``ArchSpec`` object — must resume its cache bundle: the
    memoized ready-step analysis is served, not recomputed."""
    net = conv_chain()
    edges = chain_edges(net)
    arch_a = small_arch(64)
    arch_b = dataclasses.replace(arch_a, word_bits=8)
    eng = OverlapEngine()
    c = cfg(mode="transform")
    optimize_network_engine(net, edges, arch_a, c, engine=eng)
    ready_a = eng._bundles[arch_a.to_key()].ready
    n_ready = len(ready_a)
    assert n_ready > 0
    optimize_network_engine(net, edges, arch_b, c, engine=eng)
    # rebuilt spec, equal content: same bundle object, no new ready entries
    arch_a2 = type(arch_a).from_dict(arch_a.to_dict())
    assert arch_a2 is not arch_a
    res = optimize_network_engine(net, edges, arch_a2, c, engine=eng)
    assert eng._bundles[arch_a2.to_key()].ready is ready_a
    assert len(ready_a) == n_ready
    ref = optimize_network_reference(net, edges, arch_a, c)
    assert res.total_ns == ref.total_ns


def test_optimize_network_matches_reference():
    """``optimize_network`` (the engine) matches the reference path."""
    net = conv_chain()
    edges = chain_edges(net)
    arch = small_arch()
    a = optimize_network(net, edges, arch, cfg(mode="transform"))
    b = optimize_network_reference(net, edges, arch, cfg(mode="transform"))
    assert a.total_ns == b.total_ns
