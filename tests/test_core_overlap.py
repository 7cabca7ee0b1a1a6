"""Overlap analysis: analytical == exhaustive (paper C2), scheduling,
transformation (C3)."""
import random

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (Edge, HeadFoldMap, HeadUnfoldMap, IdentityMap,
                        LayerSpec, WeightMap, analyze, chain_edges, describe,
                        dram_pim, evaluate_chain, heuristic_mapping, matmul,
                        overlapped_end, random_mapping,
                        ready_steps_analytical, ready_steps_exhaustive,
                        schedule_with_ready, transform_schedule)


def small_arch(cols=8):
    return dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=cols)


def pair(seed, P=6, Q=6, C1=2, K1=4, K2=4, R=3):
    rng = random.Random(seed)
    lp = LayerSpec("p", K=K1, C=C1, P=P, Q=Q, R=R, S=R, pad=R // 2)
    lc = LayerSpec("c", K=K2, C=K1, P=P, Q=Q, R=R, S=R, pad=R // 2)
    arch = small_arch(4)
    mp = random_mapping(lp, arch, rng, max_steps=256)
    mc = random_mapping(lc, arch, rng, max_steps=256)
    return mp, mc


@pytest.mark.parametrize("seed", range(6))
def test_ready_analytical_equals_exhaustive(seed):
    mp, mc = pair(seed)
    sa, ra = ready_steps_analytical(mp, mc)
    se, re = ready_steps_exhaustive(mp, mc)
    assert np.array_equal(ra, re)
    assert np.array_equal(sa[~ra], se[~ra])


@given(seed=st.integers(0, 10 ** 6))
@settings(max_examples=15, deadline=None)
def test_property_ready_steps(seed):
    rng = random.Random(seed)
    mp, mc = pair(seed, P=rng.choice([4, 6]), Q=4,
                  K1=rng.choice([2, 4]), K2=2, R=rng.choice([1, 3]))
    sa, ra = ready_steps_analytical(mp, mc)
    se, re = ready_steps_exhaustive(mp, mc)
    assert np.array_equal(sa[~ra], se[~re])


def test_stride2_and_padding_edges():
    """Strided consumer + padding: edge spaces may be ready at t=0."""
    lp = LayerSpec("p", K=4, C=2, P=8, Q=8, R=3, S=3, pad=1)
    lc = LayerSpec("c", K=2, C=4, P=4, Q=4, R=3, S=3, stride=2, pad=1)
    arch = small_arch(4)
    rng = random.Random(7)
    mp = random_mapping(lp, arch, rng, 256)
    mc = random_mapping(lc, arch, rng, 256)
    sa, ra = ready_steps_analytical(mp, mc)
    se, re = ready_steps_exhaustive(mp, mc)
    assert np.array_equal(sa[~ra], se[~re])


def test_schedule_with_ready_recurrence():
    """Closed form == explicit recurrence."""
    rng = np.random.RandomState(0)
    ready = rng.uniform(0, 100, size=(3, 17))
    L = 7.0
    fin = schedule_with_ready(ready, L)
    for b in range(3):
        end = 0.0
        for t in range(17):
            end = max(end, ready[b, t]) + L
            assert fin[b, t] == pytest.approx(end)


def test_overlap_improves_or_equals_sequential():
    mp, mc = pair(3)
    pp, pc = analyze(mp), analyze(mc)
    fin_step = (np.arange(mp.n_steps) + 1.0) * pp.step_ns
    step, r0 = ready_steps_analytical(mp, mc)
    ready = np.where(r0, 0.0, fin_step[step] + pp.tile_move_ns)
    end_overlap = overlapped_end(ready, pc.step_ns)
    end_seq = pp.compute_ns + pc.compute_ns
    assert end_overlap <= end_seq + pp.tile_move_ns + 1e-6


def test_transform_never_worse_than_plain_overlap():
    """Round-robin re-allocation by ready time is at least as good as the
    original allocation when relocation is free, and valid otherwise."""
    mp, mc = pair(5)
    pp, pc = analyze(mp), analyze(mc)
    fin_step = (np.arange(mp.n_steps) + 1.0) * pp.step_ns
    step, r0 = ready_steps_analytical(mp, mc)
    ready = np.where(r0, 0.0, fin_step[step])
    tr = transform_schedule(ready, pc.step_ns, tile_move_ns=0.0)
    assert tr.end_ns <= overlapped_end(ready, pc.step_ns) + 1e-6
    assert 0.0 <= tr.moved_frac <= 1.0
    # finish array covers every original space exactly once
    assert tr.finish_ns.shape == ready.shape
    assert np.all(tr.finish_ns > 0)


def test_transform_respects_ready_times():
    ready = np.array([[0.0, 50.0, 10.0, 90.0]])
    tr = transform_schedule(ready, step_ns=5.0)
    # each space finishes at least ready + one step after its ready time
    assert np.all(tr.finish_ns >= ready + 5.0 - 1e-9)


def test_transform_sorted_ready_balances_banks():
    """n equal-ready spaces over b banks finish in ceil(n/b) steps."""
    ready = np.zeros((2, 8))  # 16 spaces, all ready at 0
    tr = transform_schedule(ready, step_ns=1.0)
    assert tr.end_ns == pytest.approx(8.0)


# ---------------------------------------------------------------------------
# CoordMap coverage: analytical == exhaustive for the attention maps
# (HeadFold / HeadUnfold / both WeightMap kinds), not just IdentityMap.
# ---------------------------------------------------------------------------

SEQ, HEADS, HD, DM = 8, 2, 4, 8


def _attn_pair(kind, seed):
    """(producer layer, consumer layer, cmap) for one attention edge."""
    rng = random.Random(seed)
    proj = matmul("proj", SEQ, DM, DM)
    qk = matmul("qk", SEQ, HD, SEQ, batch=HEADS)
    av = matmul("av", SEQ, SEQ, HD, batch=HEADS)
    out = matmul("out", SEQ, DM, DM)
    pairs = {
        "headfold": (proj, qk, HeadFoldMap(SEQ, HD)),     # qk <- q_proj
        "headunfold": (av, out, HeadUnfoldMap(SEQ, HD)),  # out <- av
        "qk_weight": (proj, qk, WeightMap(SEQ, HD, "qk_weight")),
        "av_weight": (proj, av, WeightMap(SEQ, HD, "av_weight")),
    }
    lp, lc, cmap = pairs[kind]
    arch = small_arch(8)
    mp = random_mapping(lp, arch, rng, 64)
    mc = random_mapping(lc, arch, rng, 64)
    return mp, mc, cmap


@pytest.mark.parametrize("kind",
                         ["headfold", "headunfold", "qk_weight",
                          "av_weight"])
@pytest.mark.parametrize("seed", range(4))
def test_attention_cmaps_analytical_equals_exhaustive(kind, seed):
    mp, mc, cmap = _attn_pair(kind, seed)
    sa, ra = ready_steps_analytical(mp, mc, cmap)
    se, re = ready_steps_exhaustive(mp, mc, cmap)
    assert np.array_equal(ra, re)
    assert np.array_equal(sa[~ra], se[~ra])


@pytest.mark.parametrize("seed", range(3))
def test_bert_network_edges_analytical_equals_exhaustive(seed):
    """Every edge of the wired BERT encoder block, as built by
    ``describe`` (covers the conservative head-boundary bounding boxes —
    DESIGN.md Section 5.3)."""
    desc = describe("bert_encoder", seq=SEQ, d_model=DM, heads=HEADS,
                    d_ff=16)
    arch = small_arch(8)
    rng = random.Random(seed)
    maps = [random_mapping(l, arch, rng, 64) for l in desc.layers]
    for i, edges in enumerate(desc.edges):
        for e in edges:
            sa, ra = ready_steps_analytical(maps[e.producer], maps[i],
                                            e.cmap)
            se, re = ready_steps_exhaustive(maps[e.producer], maps[i],
                                            e.cmap)
            assert np.array_equal(ra, re), (i, e.producer)
            assert np.array_equal(sa[~ra], se[~ra]), (i, e.producer)


# ---------------------------------------------------------------------------
# Exhaustive-path sentinel regression: a consumer space whose projected
# rectangle intersects NO producer space (e.g. a channel overhang, where the
# consumer reads more input channels than the producer computes) must come
# out ready-at-0, not carrying the -1 search sentinel — ``fin_step[step]``
# would wrap -1 to the LAST producer step and charge the space "ready at
# producer completion".
# ---------------------------------------------------------------------------

def _overhang_pair(seed=0):
    """Consumer C=8 > producer K=4: tiles with C-offset >= 4 project to
    producer-K intervals beyond the producer's output range."""
    lp = LayerSpec("p", K=4, C=2, P=6, Q=6, R=3, S=3, pad=1)
    lc = LayerSpec("c", K=4, C=8, P=6, Q=6, R=3, S=3, pad=1)
    arch = dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=4)
    rng = random.Random(seed)
    mp = random_mapping(lp, arch, rng, 256)
    mc = random_mapping(lc, arch, rng, 256)
    return lp, mp, mc


def test_exhaustive_no_intersection_is_ready_at_zero():
    from repro.core.overlap import consumer_tiles

    lp, mp, mc = _overhang_pair(0)
    lo, hi = consumer_tiles(mc)
    plo, _phi, r0 = IdentityMap().to_producer(lp, mc.layer, lo, hi)
    none = (plo["K"] >= lp.K) & ~r0
    assert none.any()   # the scenario actually occurs in this pair
    step, ready0 = ready_steps_exhaustive(mp, mc)
    # pre-fix: step[none] == -1 and ready0[none] stayed False
    assert step.min() >= 0
    assert ready0[none].all()
    # intersecting spaces are untouched by the clamp
    sa, ra = ready_steps_analytical(mp, mc)
    both = ~ready0 & ~ra
    assert np.array_equal(step[both], sa[both])


def test_exhaustive_sentinel_spaces_not_charged_producer_completion():
    """Scheduling consequence of the fix: the overhang spaces must not
    inherit the producer's last finish time through index wraparound."""
    _lp, mp, mc = _overhang_pair(0)
    pp, pc = analyze(mp), analyze(mc)
    fin_step = (np.arange(mp.n_steps) + 1.0) * pp.step_ns
    step, r0 = ready_steps_exhaustive(mp, mc)
    ready = np.where(r0, 0.0, fin_step[step] + pp.tile_move_ns)
    none_ready = ready[r0]
    assert np.all(none_ready == 0.0)
    # and the resulting schedule is no worse than the pre-fix wraparound
    wrap = np.where(r0, fin_step[-1], ready)
    assert (overlapped_end(ready, pc.step_ns)
            <= overlapped_end(wrap, pc.step_ns) + 1e-9)


# ---------------------------------------------------------------------------
# digit_scan property coverage: the m == 1 fast path must agree with the
# general multi-digit scan and with brute-force interval enumeration.
# ---------------------------------------------------------------------------

def _digit_brute(loops, lo, hi):
    xs = np.arange(lo, hi + 1)
    tot = np.zeros(xs.shape)
    for n, blk, w in loops:
        tot = tot + float(w) * ((xs // blk) % n)
    return float(tot.max())


@given(seed=st.integers(0, 10 ** 6))
@settings(max_examples=30, deadline=None)
def test_property_digit_scan_single_loop_fast_path(seed):
    from repro.core.overlap import digit_scan

    rng = random.Random(seed)
    n1 = rng.choice([2, 3, 4, 5, 8])
    blk = rng.choice([1, 2, 3, 4])
    w1 = rng.choice([0, 1, 3, 7])
    dim = n1 * blk
    lo = rng.randrange(dim)
    hi = rng.randrange(lo, dim)
    loops = [(n1, blk, w1)]
    los = np.array([lo])
    his = np.array([hi])
    fast = digit_scan(loops, los, his)          # m == 1 branch
    # size-1 dummy loop contributes 0 everywhere but forces the general
    # multi-digit path over the same interval
    general = digit_scan(loops + [(1, 1, 0)], los, his)
    assert float(fast[0]) == float(general[0])
    assert float(fast[0]) == _digit_brute(loops, lo, hi)


@given(seed=st.integers(0, 10 ** 6))
@settings(max_examples=30, deadline=None)
def test_property_digit_scan_multi_loop_vs_brute(seed):
    from repro.core.overlap import digit_scan

    rng = random.Random(seed)
    m = rng.choice([2, 3])
    sizes = [rng.choice([2, 3, 4]) for _ in range(m)]
    # mixed-radix decomposition of the dim: loop j owns blocks of the
    # product of the sizes inside it; like rect_loops, the list is
    # outermost (most significant digit) first — the scan's prefix /
    # suffix families rely on that ordering
    blks, b = [], 1
    for sz in sizes:
        blks.append(b)
        b *= sz
    dim = b
    loops = [(sz, blk, rng.choice([0, 1, 2, 5]))
             for sz, blk in zip(sizes, blks)][::-1]
    lo = rng.randrange(dim)
    hi = rng.randrange(lo, dim)
    got = digit_scan(loops, np.array([lo]), np.array([hi]))
    assert float(got[0]) == _digit_brute(loops, lo, hi)


@pytest.mark.parametrize("seed", range(4))
def test_mode_ordering_on_fixed_chain(seed):
    """transform <= overlap <= original total_ns for the same mappings on
    a fixed seeded chain (Fig 4 / Fig 10 trend as an invariant)."""
    net = [
        LayerSpec("l0", K=8, C=4, P=8, Q=8, R=3, S=3, pad=1),
        LayerSpec("l1", K=8, C=8, P=8, Q=8, R=3, S=3, pad=1),
        LayerSpec("l2", K=16, C=8, P=4, Q=4, R=3, S=3, stride=2, pad=1),
    ]
    arch = dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=64)
    rng = random.Random(seed)
    maps = [random_mapping(l, arch, rng, 512) for l in net]
    edges = chain_edges(net)
    t = {m: evaluate_chain(maps, edges, m).total_ns
         for m in ("original", "overlap", "transform")}
    assert t["transform"] <= t["overlap"] + 1e-6
    assert t["overlap"] <= t["original"] + 1e-6
