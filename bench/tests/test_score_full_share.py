"""The reader of the closed-form scoring stage of all-``FullMap`` layers,
on hand-built runs, and the cell that reports it."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness  # noqa: E402

NAME = "score_full_share.search"


@pytest.mark.parametrize("run,want", [
    ({"window_s": 10.0, "counters": {"engine.score_full_s": 0.5,
                                     "engine.score_dense_s": 3.0}}, 5.0),
    ({"window_s": 4.0, "counters": {"engine.score_full_s": 0.0}}, 0.0),
])
def test_reader_reads_the_counter_over_the_window(run, want):
    assert harness.reader(NAME)(run) == pytest.approx(want)


@pytest.mark.parametrize("run", [
    {"window_s": 10.0, "counters": {"engine.score_dense_s": 3.0}},
    {"window_s": 10.0},
])
def test_reader_is_silent_without_the_counter(run):
    assert harness.reader(NAME)(run) is None


def test_granite_cell_reports_it_when_traced():
    m = harness.load_manifest()
    cell = "granite_moe.decode4k.search-c8"
    assert NAME in {x["name"] for x in harness.metrics_for(m, cell, True)}
    assert NAME not in {x["name"] for x in harness.metrics_for(m, cell, False)}
