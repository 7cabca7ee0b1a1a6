"""Metric readers and the trace reduction on synthetic samples."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, trace_reduce  # noqa: E402


def read(name, run):
    return harness.reader(name)(run)


def test_search_s_is_window_over_completed():
    run = {"window_s": 45.5, "completed": 13}
    assert read("search_s", run) == 45.5 / 13
    assert read("search_s", {"window_s": 1.0, "completed": 0}) is None


@pytest.mark.parametrize("name,q", [("req_p50_ms", 0.5), ("req_p90_ms", 0.9)])
def test_latency_tail_is_over_every_request(name, q):
    # 100 requests: 0.001..0.100 s; nearest-rank percentile of all of them
    reqs = [{"latency_s": (i + 1) / 1000, "code": 200} for i in range(100)]
    want = {0.5: 50.5, 0.9: 90.0}[q]
    assert read(name, {"requests": reqs}) == pytest.approx(want)
    # a failed request counts as infinitely late, not as left out
    reqs[0] = {"latency_s": 0.0001, "code": 500}
    failed = read(name, {"requests": reqs})
    assert failed >= read(name, {"requests": reqs[1:]})


def test_p90_counts_the_slowest_tenth():
    reqs = [{"latency_s": 1.0, "code": 200}] * 90 \
        + [{"latency_s": 9.0, "code": 200}] * 10
    assert read("req_p90_ms", {"requests": reqs}) == 1000.0
    reqs = reqs[:89] + [{"latency_s": 9.0, "code": 200}] * 11
    assert read("req_p90_ms", {"requests": reqs}) == 9000.0


def test_shares_and_stage_times():
    run = {"counters": {"engine.dense_scored": 48,
                        "engine.batch_scored": 912}}
    assert read("dense_share.search", run) == pytest.approx(5.0)
    assert read("dense_share.search", {"counters": {}}) is None
    flight = [{"served_from": "memo", "outcome": "ok", "admit_wait_s": 0.0,
               "evaluate_s": 0.0}] * 3 + [
        {"served_from": "search", "outcome": "ok", "admit_wait_s": w,
         "evaluate_s": e} for w, e in ((0.1, 1.0), (0.2, 2.0), (0.3, 3.0))]
    run = {"flight": flight}
    assert read("memo_hit_share.serve", run) == pytest.approx(50.0)
    assert read("evaluate_p50_ms.serve", run) == pytest.approx(2000.0)
    assert read("queue_wait_p90_ms.serve", run) == pytest.approx(300.0)
    spans = {"dse.sweep": [10.0, 10.0], "dse.evaluate_batch": [9.5, 9.5]}
    assert read("explore_share.search", {"spans": spans}) \
        == pytest.approx(5.0)
    assert read("explore_share.search", {"spans": {}}) is None


def test_idle_share_reader_is_silent_without_a_trace():
    assert read("device_idle_share.search", {}) is None
    assert read("device_idle_share.serve", {"trace": {"idle_share": 0.25}}) \
        == pytest.approx(25.0)


def test_union_and_gaps():
    cover = trace_reduce.union([(5, 9), (0, 2), (1, 3), (8, 10), (12, 12)])
    assert cover == [(0, 3), (5, 10)]
    assert trace_reduce.gaps(cover, 0, 15) == [(3, 5), (10, 15)]
    assert trace_reduce.gaps(cover, -2, 4) == [(-2, 0), (3, 4)]


def _planes():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_f", 100, 400)]},
        {"name": "XLA Ops", "events": [("fusion", 100, 100),
                                       ("fusion", 150, 100),
                                       ("copy", 400, 50),
                                       ("late", 990, 100)]}]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [("window", 0, 1000),
                                      ("search", 0, 500),
                                      ("search", 500, 500)]}]}
    return [host, dev]


def test_reduce_planes_busy_is_union_of_ops_inside_window():
    out = trace_reduce.reduce_planes(_planes(), ("search",), 0, 1000)
    # ops cover [100, 250) and [400, 450) and [990, 1000) inside the window
    assert out["busy_s"] == pytest.approx(210e-9)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["idle_share"] == pytest.approx(1 - 0.21)
    assert out["device_ops"][0][0] == "fusion"
    longest = out["idle_gaps"][0]
    assert longest[0] == "search" and longest[1] == pytest.approx(540e-9)


def test_reduce_planes_needs_a_device():
    planes = [p for p in _planes() if not p["name"].startswith("/device")]
    assert trace_reduce.reduce_planes(planes, ("search",), 0, 10) is None


FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "host_cpu.xplane.pb")


def test_recorded_trace_is_read_and_reduced():
    """A trace recorded on the CPU backend: three jitted matmuls inside
    ``search`` annotations inside a ``window`` annotation. The CPU has no
    device plane, so the reduction reads nothing; with a device plane
    laid over its annotations it reads the ops' union."""
    planes = trace_reduce.load_planes(FIXTURE)
    host = [e for p in planes if p["name"] == "/host:CPU"
            for line in p["lines"] for e in line["events"]]
    win = [e for e in host if e[0] == "window"]
    searches = sorted(e for e in host if e[0] == "search")
    assert len(win) == 1 and len(searches) == 3
    assert trace_reduce.reduce_planes(planes, ("search",)) is None
    t0, t1 = win[0][1], win[0][1] + win[0][2]
    ops = [("fusion", s + d // 4, d // 2) for _, s, d in searches]
    dev = {"name": "/device:TPU:0",
           "lines": [{"name": "XLA Ops", "events": ops}]}
    out = trace_reduce.reduce_planes(planes + [dev], ("search",), t0, t1)
    assert out["busy_s"] == pytest.approx(sum(d for _, _, d in ops) / 1e9)
    assert out["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert {n for n, _ in out["idle_gaps"]} <= {"search",
                                                "no benchmark span"}
