"""Energy accounting of the overlap transformation (DESIGN.md Section 9).

Property tests pin the algebra of ``transform_schedule``'s
``moved_bytes`` / ``move_energy_pj`` extension (zero-move => zero
energy, monotonicity in the tile footprint, latency invariance vs the
pre-energy code path), and a golden regression pins the per-layer
compute/IO/move energy split of resnet18 on the paper's default
``dram_pim()`` so perf-model refactors cannot silently drift the energy
model.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (SearchConfig, combine_objective, describe,
                        dram_pim, evaluate_chain, heuristic_mapping,
                        move_energy_pj, transform_schedule)


def ready_matrix(seed: int, nb: int, nt: int) -> np.ndarray:
    rng = np.random.RandomState(seed)
    return rng.uniform(0.0, 50.0, size=(nb, nt))


# ---------------------------------------------------------------------------
# Properties of transform_schedule's energy accounting.
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 10 ** 6), nt=st.integers(1, 9))
@settings(max_examples=15, deadline=None)
def test_single_bank_never_moves_never_charges(seed, nt):
    """nb == 1: round-robin re-allocation cannot re-home anything, so
    moved_frac == 0 => moved_bytes == move_energy_pj == 0 regardless of
    the footprint."""
    tr = transform_schedule(ready_matrix(seed, 1, nt), step_ns=3.0,
                            tile_move_ns=1.0, tile_bytes=4096.0,
                            move_pj_per_byte=6.4)
    assert tr.moved_frac == 0.0
    assert tr.moved_bytes == 0.0
    assert tr.move_energy_pj == 0.0


@given(seed=st.integers(0, 10 ** 6), nb=st.integers(1, 4),
       nt=st.integers(1, 8))
@settings(max_examples=15, deadline=None)
def test_zero_footprint_zero_energy(seed, nb, nt):
    """moved_frac may be > 0, but tile_bytes == 0 charges nothing (the
    default — i.e. every pre-energy call site)."""
    tr = transform_schedule(ready_matrix(seed, nb, nt), step_ns=2.0,
                            tile_move_ns=1.5, move_pj_per_byte=6.4)
    assert tr.moved_bytes == 0.0
    assert tr.move_energy_pj == 0.0


@given(seed=st.integers(0, 10 ** 6), nb=st.integers(1, 4),
       nt=st.integers(1, 8))
@settings(max_examples=15, deadline=None)
def test_move_energy_monotone_in_tile_bytes(seed, nb, nt):
    ready = ready_matrix(seed, nb, nt)
    prev = -1.0
    for tb in (0.0, 1.0, 64.0, 4096.0):
        tr = transform_schedule(ready, step_ns=2.0, tile_move_ns=1.0,
                                tile_bytes=tb, move_pj_per_byte=6.4)
        assert tr.move_energy_pj >= prev
        prev = tr.move_energy_pj


@given(seed=st.integers(0, 10 ** 6), nb=st.integers(1, 4),
       nt=st.integers(1, 8))
@settings(max_examples=15, deadline=None)
def test_latency_results_invariant_under_tile_bytes(seed, nb, nt):
    """The schedule (end/finish/moved_frac) must be byte-for-byte what
    the pre-energy code path produced, for ANY footprint: tile_bytes
    feeds accounting only."""
    ready = ready_matrix(seed, nb, nt)
    base = transform_schedule(ready, step_ns=2.0, tile_move_ns=1.0)
    for tb in (0.0, 64.0, 4096.0):
        tr = transform_schedule(ready, step_ns=2.0, tile_move_ns=1.0,
                                tile_bytes=tb, move_pj_per_byte=6.4)
        assert tr.end_ns == base.end_ns
        assert np.array_equal(tr.finish_ns, base.finish_ns)
        assert tr.moved_frac == base.moved_frac


@given(seed=st.integers(0, 10 ** 6), nb=st.integers(1, 4),
       nt=st.integers(1, 8))
@settings(max_examples=15, deadline=None)
def test_moved_bytes_and_energy_consistency(seed, nb, nt):
    """moved_bytes == (#moved) * tile_bytes and energy == bytes * pJ/B;
    a constant per-space footprint array must equal the scalar path."""
    ready = ready_matrix(seed, nb, nt)
    tb, e = 96.0, 6.4
    tr = transform_schedule(ready, step_ns=2.0, tile_move_ns=1.0,
                            tile_bytes=tb, move_pj_per_byte=e)
    n_moved = round(tr.moved_frac * ready.size)
    assert tr.moved_bytes == n_moved * tb
    assert tr.move_energy_pj == tr.moved_bytes * e
    arr = transform_schedule(ready, step_ns=2.0, tile_move_ns=1.0,
                             tile_bytes=np.full((nb, nt), tb),
                             move_pj_per_byte=e)
    assert arr.moved_bytes == tr.moved_bytes
    assert arr.move_energy_pj == tr.move_energy_pj


def test_per_space_footprints_bounded_by_extremes():
    """Heterogeneous per-space footprints: moved_bytes lies between the
    all-min and all-max scalar cases (the accounting really reads the
    per-space array, not an average)."""
    ready = ready_matrix(3, 3, 7)
    rng = np.random.RandomState(7)
    tb = rng.uniform(10.0, 100.0, size=(3, 7))
    got = transform_schedule(ready, step_ns=2.0, tile_bytes=tb)
    lo = transform_schedule(ready, step_ns=2.0, tile_bytes=float(tb.min()))
    hi = transform_schedule(ready, step_ns=2.0, tile_bytes=float(tb.max()))
    assert lo.moved_bytes <= got.moved_bytes <= hi.moved_bytes
    if got.moved_frac > 0:
        assert lo.moved_bytes < hi.moved_bytes


# ---------------------------------------------------------------------------
# Objective scalarization + the perf-model hook.
# ---------------------------------------------------------------------------

def test_combine_objective_semantics():
    lat, en = 1000.0, 250.0
    assert combine_objective("latency", lat, en) == lat
    assert combine_objective("energy", lat, en) == en
    assert combine_objective("edp", lat, en) == lat * en
    assert combine_objective("blend", lat, en, 0.0) == lat
    assert combine_objective("blend", lat, en, 1.0) == en
    mid = combine_objective("blend", lat, en, 0.5)
    assert min(lat, en) <= mid <= max(lat, en)
    with pytest.raises(ValueError):
        combine_objective("nonsense", lat, en)


def test_move_energy_hook_matches_io_energy_scale():
    arch = dram_pim()
    assert move_energy_pj(arch, 1.0) == 8 * arch.timing.e_io
    assert move_energy_pj(arch, 100.0) == 100 * 8 * arch.timing.e_io


def test_layer_perf_energy_decomposition():
    """energy_pj must stay exactly compute + IO (the pre-energy value),
    with the split and the transform inputs exposed alongside."""
    from repro.core import analyze
    arch = dram_pim()
    desc = describe("resnet18")
    m = heuristic_mapping(desc.layers[0], arch, 16384)
    p = analyze(m)
    assert p.energy_pj == p.compute_energy_pj + p.io_energy_pj
    assert p.compute_energy_pj > 0 and p.io_energy_pj > 0
    assert p.tile_bytes > 0
    assert p.move_pj_per_byte == move_energy_pj(arch, 1.0)
    # tile time and tile energy describe the same footprint
    ext = m.tile_extent
    tile_out = ext["K"] * ext["P"] * ext["Q"]
    assert p.tile_bytes == tile_out * arch.word_bytes


# ---------------------------------------------------------------------------
# Golden regression: resnet18 on dram_pim(), heuristic mappings,
# transform mode. Pins the compute/IO/move energy split per layer at the
# current model values — any perf_model/transform refactor that shifts
# the energy model must update these numbers *consciously*.
# ---------------------------------------------------------------------------

GOLDEN_RESNET18_DRAM = [
    # (layer, compute_energy_pj, io_energy_pj, move_energy_pj)
    ("conv1", 118538524016640.0, 10276044.8, 0.0),
    ("s1b0c1", 116119370465280.0, 2569011.2, 2384793.6),
    ("s1b0c2", 116119370465280.0, 2569011.2, 2388684.8000000003),
    ("s1b1c1", 116119370465280.0, 2569011.2, 2390937.6),
    ("s1b1c2", 116119370465280.0, 2569011.2, 2385920.0),
    ("s2b0c1", 58059685232640.0, 1284505.6, 1192755.2),
    ("s2b0c2", 116119370465280.0, 1284505.6, 1184768.0),
    ("s2b0ds", 6451076136960.0, 1284505.6, 127795.20000000001),
    ("s2b1c1", 116119370465280.0, 1284505.6, 1184768.0),
    ("s2b1c2", 116119370465280.0, 1284505.6, 1184768.0),
    ("s3b0c1", 58059685232640.0, 642252.8, 592076.8),
    ("s3b0c2", 116119370465280.0, 642252.8, 596377.6),
    ("s3b0ds", 6451076136960.0, 642252.8, 596377.6),
    ("s3b1c1", 116119370465280.0, 642252.8, 596377.6),
    ("s3b1c2", 116119370465280.0, 642252.8, 596377.6),
    ("s4b0c1", 58059685232640.0, 321126.4, 298188.8),
    ("s4b0c2", 116119370465280.0, 321126.4, 298112.0),
    ("s4b0ds", 6451076136960.0, 321126.4, 280985.60000000003),
    ("s4b1c1", 116119370465280.0, 321126.4, 298112.0),
    ("s4b1c2", 116119370465280.0, 321126.4, 298112.0),
]


def _golden_chain():
    arch = dram_pim()
    desc = describe("resnet18")
    maps = [heuristic_mapping(l, arch, 16384) for l in desc.layers]
    return evaluate_chain(maps, desc.edges, "transform")


def test_golden_resnet18_energy_split():
    res = _golden_chain()
    assert len(res.layers) == len(GOLDEN_RESNET18_DRAM)
    for lr, (name, compute, io, move) in zip(res.layers,
                                             GOLDEN_RESNET18_DRAM):
        assert lr.mapping.layer.name == name
        assert lr.perf.compute_energy_pj == pytest.approx(compute,
                                                          rel=1e-12)
        assert lr.perf.io_energy_pj == pytest.approx(io, rel=1e-12)
        assert lr.move_energy_pj == pytest.approx(move, rel=1e-12)
        assert lr.energy_pj == lr.perf.energy_pj + lr.move_energy_pj


def test_golden_resnet18_summary_breakdown():
    """NetworkResult.summary() reports the same decomposition, summed."""
    res = _golden_chain()
    s = res.summary()
    exp_compute = sum(g[1] for g in GOLDEN_RESNET18_DRAM)
    exp_io = sum(g[2] for g in GOLDEN_RESNET18_DRAM)
    exp_move = sum(g[3] for g in GOLDEN_RESNET18_DRAM)
    assert s["compute_energy_pj"] == pytest.approx(exp_compute, rel=1e-12)
    assert s["io_energy_pj"] == pytest.approx(exp_io, rel=1e-12)
    assert s["move_energy_pj"] == pytest.approx(exp_move, rel=1e-12)
    assert s["energy_pj"] == pytest.approx(
        exp_compute + exp_io + exp_move, rel=1e-12)
    assert s["edp_ns_pj"] == pytest.approx(s["total_ns"] * s["energy_pj"],
                                           rel=1e-12)
    # skip-connection layers move real data in transform mode; the stem
    # (no producer) moves nothing — the split is not vacuous
    assert s["move_energy_pj"] > 0
    assert res.layers[0].move_energy_pj == 0.0


def test_search_config_rejects_unknown_objective():
    with pytest.raises(AssertionError):
        SearchConfig(objective="joules")
    with pytest.raises(AssertionError):
        SearchConfig(objective="blend", blend_alpha=1.5)
