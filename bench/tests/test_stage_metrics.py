"""Readers of the search stages' shares of the window and of the
engine-lock wait, on hand-built runs: the right number where the
program wrote its span, counter or flight field, and None where it did
not (a program that lacks them, as older ones do)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness  # noqa: E402

_SPANS = {"search.layer": [4.0, 4.0], "search.candidates": [0.5, 0.25],
          "search.commit": [1.0, 0.5, 0.5]}
_COUNTERS = {"engine.score_batch_s": 1.5, "engine.score_dense_s": 3.0,
             "engine.batch_scored": 100, "engine.dense_scored": 10}
_FLIGHT = [{"served_from": "memo", "lock_wait_s": 0.0}] * 5 + [
    {"served_from": "search", "lock_wait_s": (i + 1) / 1000}
    for i in range(10)]


@pytest.mark.parametrize("name,run,want,bare", [
    ("candidates_share.search", {"window_s": 10.0, "spans": _SPANS}, 7.5,
     {"window_s": 10.0, "spans": {"search.layer": [4.0]}}),
    ("commit_share.search", {"window_s": 10.0, "spans": _SPANS}, 20.0,
     {"window_s": 10.0, "spans": {}}),
    ("score_batch_share.search",
     {"window_s": 10.0, "counters": _COUNTERS}, 15.0,
     {"window_s": 10.0, "counters": {"engine.batch_scored": 100}}),
    ("score_dense_share.search",
     {"window_s": 10.0, "counters": _COUNTERS}, 30.0,
     {"window_s": 10.0}),
    ("lock_wait_p90_ms.serve", {"flight": _FLIGHT}, 9.0,
     {"flight": [{"served_from": "search", "evaluate_s": 1.0}]}),
])
def test_stage_reader(name, run, want, bare):
    read = harness.reader(name)
    assert read(run) == pytest.approx(want)
    assert read(bare) is None
