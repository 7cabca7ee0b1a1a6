"""Data space generation: analytical == exhaustive (paper C1), coverage,
disjointness, point location."""
import random

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (LayerSpec, dram_pim, generate_analytical,
                        generate_exhaustive, heuristic_mapping,
                        locate_finish, locate_finish_exhaustive,
                        random_mapping)
from repro.core.workload import OUTPUT_DIMS


def small_arch(cols=8):
    return dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=cols)


def small_layer():
    return LayerSpec("l", K=4, C=4, P=8, Q=8, R=3, S=3, pad=1)


@pytest.mark.parametrize("seed", range(10))
def test_analytical_equals_exhaustive(seed):
    m = random_mapping(small_layer(), small_arch(), random.Random(seed),
                       max_steps=512)
    a = generate_analytical(m)
    e = generate_exhaustive(m)
    assert a.equals(e)


def test_output_coverage_and_step_disjointness():
    """Union of all spaces covers the output tensor exactly; spaces of a
    single time step are pairwise disjoint in output coords (each step
    computes distinct output partials per bank)."""
    m = heuristic_mapping(small_layer(), small_arch())
    ds = generate_analytical(m)
    layer = m.layer
    counts = np.zeros((layer.K, layer.P, layer.Q), dtype=np.int64)
    for b in range(ds.n_banks):
        for t in range(ds.n_steps):
            r = ds.rect(b, t)
            counts[r["K"][0]:r["K"][1], r["P"][0]:r["P"][1],
                   r["Q"][0]:r["Q"][1]] += 1
    # every output element visited the same number of times (= number of
    # temporal reduction iterations mapped above the tile)
    assert counts.min() == counts.max() > 0


@given(seed=st.integers(0, 10 ** 6))
@settings(max_examples=25, deadline=None)
def test_property_analytical_equals_exhaustive(seed):
    rng = random.Random(seed)
    layer = LayerSpec("l", K=rng.choice([2, 4, 6]), C=rng.choice([2, 3]),
                      P=rng.choice([4, 6]), Q=rng.choice([4, 6]),
                      R=rng.choice([1, 3]), S=rng.choice([1, 3]), pad=1)
    m = random_mapping(layer, small_arch(4), rng, max_steps=256)
    assert generate_analytical(m).equals(generate_exhaustive(m))


@given(seed=st.integers(0, 10 ** 6))
@settings(max_examples=20, deadline=None)
def test_property_locate_finish_matches_exhaustive(seed):
    """Analytical point location returns the latest intersecting space —
    the paper's core overlap lemma (Eq 5/6 vs O(N*M) scan)."""
    rng = random.Random(seed)
    layer = LayerSpec("l", K=rng.choice([2, 4]), C=2, P=4, Q=4,
                      R=rng.choice([1, 3]), S=1, pad=0)
    m = random_mapping(layer, small_arch(4), rng, max_steps=256)
    ds = generate_analytical(m)
    for _ in range(5):
        k = rng.randrange(layer.K)
        p = rng.randrange(layer.P)
        q = rng.randrange(layer.Q)
        coords = {d: np.array([v]) for d, v in
                  zip(OUTPUT_DIMS, (k, p, q))}
        bank_a, step_a = locate_finish(m, coords)
        lo = {"K": k, "P": p, "Q": q}
        hi = {"K": k + 1, "P": p + 1, "Q": q + 1}
        bank_e, step_e = locate_finish_exhaustive(ds, lo, hi)
        assert step_a[0] == step_e, (m.pretty(), (k, p, q))


def test_locate_finish_reduction_at_last_iteration():
    """An output coordinate's finish step includes all reduction steps:
    locate_finish must point at the LAST step touching that coordinate."""
    m = heuristic_mapping(small_layer(), small_arch())
    ds = generate_analytical(m)
    coords = {"K": np.array([0]), "P": np.array([0]), "Q": np.array([0])}
    bank, step = locate_finish(m, coords)
    # exhaustive max over intersecting spaces
    _, step_e = locate_finish_exhaustive(
        ds, {"K": 0, "P": 0, "Q": 0}, {"K": 1, "P": 1, "Q": 1})
    assert step[0] == step_e
