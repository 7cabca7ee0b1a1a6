"""Differential tests for the batched candidate scorer (DESIGN.md §6).

The reference per-candidate path (``search._score_forward``,
``optimize_network_reference``) is the oracle: every batched score must be
*bit-identical* to it — the batch restructuring only reorders exact
integer/float operations that are reassociation-safe (see DESIGN.md §6
for the argument per stage).
"""
import random

import numpy as np
import pytest

from repro.core import Edge, FullMap, SearchConfig, chain_edges, describe, \
    dram_pim, optimize_network, optimize_network_reference
from repro.core.dataspace import (rect_bounds, rect_bounds_separable,
                                  rect_bounds_separable_stacked,
                                  rect_bounds_stacked)
from repro.core.engine import OverlapEngine, optimize_network_engine
from repro.core.overlap import stream_tail_fraction, stream_tail_fractions
from repro.core.search import LayerSpec, _consumers_of, _score_forward, \
    candidates
from repro.core.transform import transform_end_grouped, transform_schedule


def _arch():
    return dram_pim(2, 2, 4)


def _pools(desc, arch, cfg):
    return [candidates(desc.layers[i], arch, cfg, salt=i)
            for i in range(len(desc.layers))]


# ---------------------------------------------------------------------------
# transform_end_grouped vs transform_schedule on dense random matrices
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_transform_end_grouped_matches_schedule(seed):
    rng = random.Random(seed)
    nb = rng.choice([1, 2, 4])
    nt = rng.choice([3, 8, 16])
    step_ns = rng.choice([1.0, 2.5])
    tile_move = rng.choice([0.0, 3.0])
    # few distinct values -> lots of ties, the regime grouping exploits
    vals_pool = sorted(rng.sample(range(0, 50), rng.choice([2, 3, 5])))
    ready = np.array([[float(rng.choice(vals_pool)) for _ in range(nt)]
                      for _ in range(nb)])
    tr = transform_schedule(ready, step_ns, tile_move)

    uniq = np.unique(ready)
    counts = np.zeros((1, uniq.size, nb), dtype=np.int64)
    for b in range(nb):
        for t in range(nt):
            counts[0, np.searchsorted(uniq, ready[b, t]), b] += 1
    end, moved = transform_end_grouped(
        uniq[None, :], counts, np.array([nt]), np.array([step_ns]),
        np.array([tile_move]))
    assert float(end[0]) == tr.end_ns
    assert int(moved[0]) == int(round(tr.moved_frac * nb * nt))


def test_transform_end_grouped_padded_batch():
    """Rows padded with zero-count value slots must not change the end."""
    ready = np.array([[0.0, 4.0, 4.0], [2.0, 2.0, 6.0]])
    tr = transform_schedule(ready, 1.5, 2.0)
    uniq = np.unique(ready)
    counts = np.zeros((1, uniq.size + 3, 2), dtype=np.int64)
    values = np.zeros((1, uniq.size + 3))
    values[0, :uniq.size] = uniq
    for b in range(2):
        for t in range(3):
            counts[0, np.searchsorted(uniq, ready[b, t]), b] += 1
    end, moved = transform_end_grouped(
        values, counts, np.array([3]), np.array([1.5]), np.array([2.0]))
    assert float(end[0]) == tr.end_ns


# ---------------------------------------------------------------------------
# stacked rect bounds vs per-candidate
# ---------------------------------------------------------------------------

def _some_mappings():
    desc = describe("resnet18")
    cfg = SearchConfig(n_candidates=5, seed=2, max_steps=1024)
    return candidates(desc.layers[1], _arch(), cfg, salt=1)


def test_rect_bounds_stacked_matches_per_candidate():
    ms = _some_mappings()
    lo_s, hi_s, offs = rect_bounds_stacked(ms)
    for j, m in enumerate(ms):
        lo, hi = rect_bounds(m)
        a, b = int(offs[j]), int(offs[j + 1])
        for d in lo:
            assert np.array_equal(lo_s[d][a:b], lo[d].reshape(-1))
            assert np.array_equal(hi_s[d][a:b], hi[d].reshape(-1))


def test_rect_bounds_separable_stacked_matches_per_candidate():
    ms = _some_mappings()
    bank_s, step_s, exts, boff, toff = rect_bounds_separable_stacked(ms)
    for j, m in enumerate(ms):
        bank, step, ext = rect_bounds_separable(m)
        b0, b1 = int(boff[j]), int(boff[j + 1])
        t0, t1 = int(toff[j]), int(toff[j + 1])
        assert exts[j] == ext
        for d in bank:
            assert np.array_equal(bank_s[d][b0:b1], bank[d])
            assert np.array_equal(step_s[d][t0:t1], step[d])


# ---------------------------------------------------------------------------
# stream_tail_fractions vs the scalar function
# ---------------------------------------------------------------------------

def test_stream_tail_fractions_matches_scalar():
    desc = describe("resnet18")
    cfg = SearchConfig(n_candidates=6, seed=0, max_steps=2048)
    for i in (0, 7, 18):
        ms = candidates(desc.layers[i], _arch(), cfg, salt=i)
        got = stream_tail_fractions(ms)
        want = [stream_tail_fraction(m) for m in ms]
        assert list(got) == want


# ---------------------------------------------------------------------------
# score_forward_batch vs the reference _score_forward, layer by layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,objective", [("overlap", "latency"),
                                            ("transform", "latency"),
                                            ("transform", "edp")])
def test_score_batch_matches_reference_resnet18(mode, objective):
    """Every batched score equals the reference score bit-for-bit, on all
    resnet18 layers (including the multi-edge residual joins) against a
    committed chain."""
    desc = describe("resnet18")
    arch = _arch()
    cfg = SearchConfig(n_candidates=6, seed=1, max_steps=1024, mode=mode,
                       objective=objective)
    res = optimize_network(desc.layers, desc.edges, arch, cfg)
    done = {i: lr for i, lr in enumerate(res.layers)}
    pools = _pools(desc, arch, cfg)
    eng = OverlapEngine()
    multi = 0
    for i, pool in enumerate(pools):
        if not desc.edges[i]:
            continue
        multi += len(desc.edges[i]) > 1
        has_cons = bool(_consumers_of(desc.edges, i))
        got = eng.score_forward_batch(i, pool, desc.edges, done, mode,
                                      has_cons, objective)
        want = [_score_forward(i, m, desc.edges, done, mode, has_cons,
                               objective) for m in pool]
        assert list(got) == want, f"layer {i} diverged"
    assert multi > 0          # the residual joins actually exercised
    assert eng._cur.sepcls    # ... through the class-histogram fast path


def test_score_batch_matches_reference_bert(mode="transform"):
    """bert_encoder's attention edges exercise the non-identity coordinate
    maps (the generic batched ready-step path + per-candidate fallback)."""
    desc = describe("bert_encoder", seq=16, d_model=8, heads=2, d_ff=16)
    arch = _arch()
    cfg = SearchConfig(n_candidates=6, seed=3, max_steps=512, mode=mode)
    res = optimize_network(desc.layers, desc.edges, arch, cfg)
    done = {i: lr for i, lr in enumerate(res.layers)}
    pools = _pools(desc, arch, cfg)
    eng = OverlapEngine()
    for i, pool in enumerate(pools):
        if not desc.edges[i]:
            continue
        has_cons = bool(_consumers_of(desc.edges, i))
        got = eng.score_forward_batch(i, pool, desc.edges, done, mode,
                                      has_cons)
        want = [_score_forward(i, m, desc.edges, done, mode, has_cons)
                for m in pool]
        assert list(got) == want, f"layer {i} diverged"


def test_score_batch_memo_returns_identical_scores():
    """Re-scoring the same pool against the same committed producers hits
    the pool memo and must return the exact same vector."""
    desc = describe("resnet18")
    arch = _arch()
    cfg = SearchConfig(n_candidates=4, seed=5, max_steps=512)
    res = optimize_network(desc.layers, desc.edges, arch, cfg)
    done = {i: lr for i, lr in enumerate(res.layers)}
    pool = candidates(desc.layers[1], arch, cfg, salt=1)
    eng = OverlapEngine()
    a = eng.score_forward_batch(1, pool, desc.edges, done, "transform")
    b = eng.score_forward_batch(1, pool, desc.edges, done, "transform")
    assert np.array_equal(a, b)
    assert a is not b         # callers own the returned vector


# ---------------------------------------------------------------------------
# end-to-end equality, engine vs reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["overlap", "transform"])
def test_e2e_engine_matches_reference(mode):
    net = [LayerSpec("a", K=8, C=3, P=16, Q=16, R=3, S=3),
           LayerSpec("b", K=8, C=8, P=16, Q=16, R=3, S=3),
           LayerSpec("c", K=4, C=8, P=8, Q=8, R=3, S=3, stride=2)]
    edges = chain_edges(net)
    arch = _arch()
    cfg = SearchConfig(n_candidates=8, seed=4, max_steps=1024, mode=mode,
                       refine_passes=1)
    a = optimize_network(net, edges, arch, cfg)
    b = optimize_network_reference(net, edges, arch, cfg)
    assert a.total_ns == b.total_ns
    assert [la.mapping.cache_key for la in a.layers] == \
        [lb.mapping.cache_key for lb in b.layers]


# ---------------------------------------------------------------------------
# all-FullMap layers: the closed-form pool scorer vs the dense paths
# ---------------------------------------------------------------------------

def _full_map_net(n_edges):
    """Two committed producers and a consumer whose edges are all
    ``FullMap``: from l1 alone, or from l0 and l1 (the ready constant is
    then the larger of the two producers')."""
    layers = [LayerSpec("l0", K=8, C=4, P=8, Q=8, R=3, S=3, pad=1),
              LayerSpec("l1", K=8, C=8, P=8, Q=8, R=3, S=3, pad=1),
              LayerSpec("l2", K=16, C=8, P=4, Q=4, R=3, S=3, stride=2,
                        pad=1)]
    full = [Edge(1, FullMap())] if n_edges == 1 else \
        [Edge(0, FullMap()), Edge(1, FullMap())]
    return layers, [[], [Edge(0)], full]


@pytest.mark.parametrize("objective", ["latency", "energy", "edp", "blend"])
@pytest.mark.parametrize("n_edges", [1, 2])
@pytest.mark.parametrize("seed", range(4))
def test_full_map_pool_scores_match_dense(seed, n_edges, objective):
    """A transform-mode pool on an all-FullMap layer is scored from one
    ready constant, never densely, and every score equals the engine's
    dense ``_score_forward_one`` and the reference ``_score_forward`` bit
    for bit — over pools that mix bank counts, with and without a
    consumer."""
    layers, edges = _full_map_net(n_edges)
    arch = _arch()
    cfg = SearchConfig(n_candidates=12, seed=seed, max_steps=512,
                       mode="transform", objective=objective)
    res = optimize_network_reference(layers, edges, arch, cfg)
    done = {i: lr for i, lr in enumerate(res.layers)}
    pool = candidates(layers[2], arch, cfg, salt=2)
    assert len({m.n_banks for m in pool}) > 1
    for has_cons in (True, False):
        eng = OverlapEngine()
        got = eng.score_forward_batch(2, pool, edges, done, "transform",
                                      has_cons, objective)
        assert eng.stats["full_scored"] == len(pool)
        assert eng.stats["dense_scored"] == 0
        dense = [OverlapEngine()._score_forward_one(
            2, m, edges, done, "transform", has_cons, objective, 0.5)
            for m in pool]
        want = [_score_forward(2, m, edges, done, "transform", has_cons,
                               objective) for m in pool]
        assert list(got) == dense == want


@pytest.mark.parametrize("objective", ["latency", "energy", "edp", "blend"])
def test_e2e_engine_matches_reference_moe_decode(objective):
    """Whole transform-mode searches of a small MoE decode scenario,
    whose expert, router and KV-append layers take the closed-form
    scorer, choose the reference's mappings and total."""
    desc = describe("granite_moe_1b_a400m_smoke:decode@64x2")
    arch = _arch()
    kw = dict(n_candidates=6, seed=7, max_steps=512, mode="transform",
              objective=objective)
    eng = OverlapEngine()
    a = optimize_network_engine(desc.layers, desc.edges, arch,
                                SearchConfig(**kw), engine=eng)
    b = optimize_network_reference(desc.layers, desc.edges, arch,
                                   SearchConfig(**kw))
    assert eng.stats["full_scored"] > 0
    assert a.total_ns == b.total_ns
    assert [la.mapping.cache_key for la in a.layers] == \
        [lb.mapping.cache_key for lb in b.layers]
