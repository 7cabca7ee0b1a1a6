"""Batched-scorer differential smoke: engine vs reference byte-equality.

Runs each configuration twice — once through the batched
``OverlapEngine`` and once through the scalar reference path
(``optimize_network_reference``) — over a small strategy x mode x
objective matrix on resnet18, and fails (exit 1) on any divergence in
``total_ns`` or the chosen mappings. This is the CI-sized version of
the bit-identity contract (DESIGN.md §6); the full differential suite
lives in ``tests/test_batched_scoring.py``.
"""
import sys
import time

from repro.core import SearchConfig, describe, dram_pim
from repro.core.search import optimize_network_reference
from repro.core.engine import OverlapEngine, optimize_network_engine

MATRIX = [
    ("overlap", "forward", "latency"),
    ("overlap", "backward", "edp"),
    ("transform", "forward", "edp"),
    ("transform", "middle_output", "latency"),
]


def main() -> int:
    desc = describe("resnet18")
    arch = dram_pim(2, 2, 4)
    ok = True
    for mode, strategy, objective in MATRIX:
        cfg = SearchConfig(mode=mode, strategy=strategy,
                           objective=objective, n_candidates=4, seed=7,
                           max_steps=1024)
        t0 = time.perf_counter()
        ref = optimize_network_reference(desc.layers, desc.edges, arch,
                                         cfg)
        t1 = time.perf_counter()
        got = optimize_network_engine(desc.layers, desc.edges, arch, cfg,
                                      engine=OverlapEngine())
        t2 = time.perf_counter()
        same = (ref.total_ns == got.total_ns
                and all(a.mapping.cache_key == b.mapping.cache_key
                        and a.end_ns == b.end_ns
                        for a, b in zip(ref.layers, got.layers)))
        ok &= same
        print(f"{mode:9s} {strategy:13s} {objective:7s} "
              f"ref={t1 - t0:5.1f}s eng={t2 - t1:5.1f}s "
              f"{'EQUAL' if same else 'DIVERGED'}")
        if not same:
            print(f"  ref total_ns={ref.total_ns!r} "
                  f"eng total_ns={got.total_ns!r}")
    print("batched-scorer differential:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
