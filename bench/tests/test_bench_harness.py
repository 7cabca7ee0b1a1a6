"""The harness end to end on the CPU, at small sizes.

Each test builds a throwaway benchmark root in a temporary directory: a
``BENCHMARK.json`` and new deployment, traffic and metric files, found by
name with no edit to a file of the benchmark. The look for a chip is
skipped by handing ``run_cell`` a device.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, program, reference  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
TINY_NET = {"scenario": {"arch": "granite_moe_1b_a400m_smoke",
                         "phase": "decode", "length": 64},
            "num_hidden_layers": 1}


def _arch():
    with open(os.path.join(harness.HERE, "configs",
                           "resnet18-dram_pim.json")) as fh:
        return json.load(fh)["arch"]


def make_root(tmp_path, traffic_params, net=None):
    """A benchmark root with one new config, traffic and metric."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    cfg = dict(net or TINY_NET, name="tiny", arch=_arch(),
               mode="transform", strategy="forward", objective="latency")
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-mix.json").write_text(
        json.dumps(traffic_params))
    (bench / "metrics" / "done_count.test.py").write_text(
        "def read(run):\n    return float(run['completed'])\n")
    for m in ("setup_s", "search_s"):
        (bench / "metrics" / f"{m}.py").write_text(
            open(os.path.join(harness.HERE, "metrics", m + ".py")).read())
    manifest = {
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny.mix", "config": "tiny",
                       "traffic": "tiny-mix", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "search_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "done_count.test", "unit": "n", "better": "higher",
             "source": "program_counter", "layer": "test",
             "moves": "search_s"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


SEARCH = {"kind": "search_loop", "n_candidates": 4, "max_steps": 64,
          "universe": 400, "judged": 2,
          "limits": {"off_pool_layers": 0, "answer_gap": 1e-10}}


def run(root, trace=False, seconds=0.6, seed=2**33 + 5):
    return harness.run_cell("tiny.mix", seed, seconds, trace,
                            time.perf_counter(), root=root, device=CPU)


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = make_root(tmp_path, SEARCH)
    out = run(root)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"search_s", "setup_s"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    traced = run(root, trace=True)
    assert traced["metrics"]["done_count.test"]["value"] >= 1
    assert traced["correct"] is True


def _scores(mode):
    from repro.core.engine import OverlapEngine
    orig = OverlapEngine.score_forward_batch

    def broken(self, *a, **kw):
        out = orig(self, *a, **kw)
        if mode == "altered":
            return -out                 # the scorer's answers altered
        half = (len(out) + 1) // 2      # half of the batch left out
        out = out.copy()
        out[half:] = np.inf
        return out
    return broken


@pytest.mark.parametrize("fault", ["altered", "half_batch"])
def test_search_fault_reads_incorrect(tmp_path, monkeypatch, fault):
    from repro.core.engine import OverlapEngine
    root = make_root(tmp_path, dict(SEARCH, judged=1),
                     net={"network": "resnet18"})
    monkeypatch.setattr(OverlapEngine, "score_forward_batch",
                        _scores(fault))
    out = run(root)
    assert out["correct"] is False
    assert out["checks"]["answer_gap"]["value"] > 1e-10


def _altered_point(monkeypatch):
    from repro.dse import explore
    orig = explore._search_arch

    def broken(*a, **kw):
        out = orig(*a, **kw)
        return dict(out, total_ns=out["total_ns"] * (1 + 1e-8))
    monkeypatch.setattr(explore, "_search_arch", broken)


DSE = {"kind": "dse_loop", "explorer": "grid", "budget": 3,
       "n_candidates": 2, "max_steps": 64, "universe": 400, "judged": 2,
       "limits": {"answer_gap": 1e-10}}
SERVE = {"kind": "serve_open_loop", "rate": 20.0, "cv": 2.0, "zipf_s": 1.0,
         "objectives": ["latency", "edp"], "seeds": 6,
         "request": {"explorer": "grid", "budget": 2, "n_candidates": 2,
                     "max_steps": 64},
         "max_workers": 1, "memo_cap": 4, "max_pending": None,
         "flight_cap": 10000, "clients": 8, "max_seconds": 5, "judged": 2,
         "limits": {"replay_mismatch": 0, "failed_requests": 0,
                    "answer_gap": 1e-10}}


@pytest.mark.parametrize("mix", [DSE, SERVE], ids=["dse", "serve"])
def test_sweep_and_serve_cells_run_and_catch_an_altered_answer(
        tmp_path, monkeypatch, mix):
    root = make_root(tmp_path, mix)
    out = run(root, seconds=0.8)
    assert out["correct"] is True, out["checks"]
    _altered_point(monkeypatch)
    out = run(root, seconds=0.8, seed=77)
    assert out["correct"] is False
    assert out["checks"]["answer_gap"]["value"] > 1e-10


def test_serve_replay_altered_reads_incorrect(tmp_path, monkeypatch):
    from repro.serve import service
    root = make_root(tmp_path, dict(SERVE, seeds=1, objectives=["latency"]))
    orig = service.MappingService.submit

    def broken(self, req):
        job = orig(self, req)
        res = getattr(job, "_result", None)
        if res is not None and res.served_from == "memo":
            job._result = service.dataclasses.replace(
                res, frontier_json=res.frontier_json + " ")
        return job
    monkeypatch.setattr(service.MappingService, "submit", broken)
    out = run(root, seconds=0.8)
    assert out["correct"] is False
    assert out["checks"]["replay_mismatch"]["value"] > 0


@pytest.mark.parametrize("net,n,steps", [
    ({"network": "resnet18"}, 3, 256), (TINY_NET, 4, 256)],
    ids=["resnet18", "granite_smoke"])
def test_reference_agrees_with_program_and_float32_control_fails(
        net, n, steps):
    cfg = dict(net, arch=_arch(), mode="transform", strategy="forward",
               objective="latency")
    dep = program.Deployment(cfg)
    plain = dep.plain_network()
    rnet = reference.Network(plain["layers"], plain["edges"])
    params = {"seed": 9, "n_candidates": n, "max_steps": steps,
              "objective": "latency"}
    ans = dep.search(dep.search_config(n, steps, 9))
    every = set(range(len(rnet.layers)))
    ref = reference.search(rnet, cfg["arch"], params, chosen=ans["chosen"],
                           check_layers=every)
    assert ref["off_pool"] == 0 and ref["choice_excess"] == 0.0
    assert abs(ans["total"] - ref["total"]) <= 1e-12 * ref["total"]
    assert abs(ans["energy"] - ref["energy"]) <= 1e-12 * ref["energy"]
    own = reference.search(rnet, cfg["arch"], params)
    assert own["total"] == pytest.approx(ans["total"], rel=1e-12)
    ctl = reference.search(rnet, cfg["arch"], params, dtype=np.float32)
    gap = max(abs(ctl["total"] - ref["total"]) / ref["total"],
              abs(ctl["energy"] - ref["energy"]) / ref["energy"])
    assert gap > 1e-10          # the float32 control fails the limit
