"""Mapping-service tests: request/response schemas, journal-as-cache,
request coalescing, deadlines, area budgets, and the job queue.

Sweeps run over a restricted ``dram_pim`` space (``space_overrides``)
with tiny per-point search budgets, mirroring ``tests/test_dse.py``'s
scale, so the whole module stays in the fast core loop. The serve
*LM* engine's compile-heavy paths live in ``test_train_substrate.py``
(slow-marked); the fast ``Engine._sample`` unit tests live here.
"""
import dataclasses
import json
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from repro.dse import ParamSpace, RunJournal, run_dse
from repro.serve import (Job, JobQueue, MappingRequest, MappingResponse,
                         MappingService, QueueFull)
from repro.serve.engine import Engine, ServeConfig


def tiny_space() -> ParamSpace:
    return ParamSpace(
        family="dram_pim",
        axes={
            "channels_per_layer": (1, 2),
            "banks_per_channel": (2, 4),
            "columns_per_bank": (64, 128),
        },
        constraints=[
            lambda p: p["channels_per_layer"] * p["banks_per_channel"] <= 4,
        ],
        defaults={"channels_per_layer": 2, "banks_per_channel": 2,
                  "columns_per_bank": 64},
    )


def tiny_request(**kw) -> MappingRequest:
    base = dict(network="resnet18", mode="transform", explorer="grid",
                budget=4, n_candidates=3, max_steps=256, seed=0)
    base.update(kw)
    return MappingRequest(**base)


def make_service(**kw) -> MappingService:
    kw.setdefault("space_overrides", {"dram_pim": tiny_space()})
    return MappingService(**kw)


# ---------------------------------------------------------------------------
# Request/response schemas.
# ---------------------------------------------------------------------------

def test_request_roundtrip_and_cache_key():
    req = tiny_request(objective="edp", area_budget_mm2=10.0)
    again = MappingRequest.from_dict(req.to_dict())
    assert again == req
    assert again.cache_key() == req.cache_key()
    # any field change changes the identity
    assert tiny_request(budget=5).cache_key() != req.cache_key()
    assert tiny_request(objective="edp",
                        area_budget_mm2=10.0,
                        deadline_s=1.0).cache_key() != req.cache_key()


def test_request_rejects_unknown_fields_and_bad_values():
    with pytest.raises(ValueError):
        MappingRequest.from_dict({"network": "resnet18", "objectiv": "edp"})
    with pytest.raises(ValueError):
        tiny_request(deadline_s=-1.0)
    with pytest.raises(ValueError):
        tiny_request(deadline_s=1.0, distributed=2)
    with pytest.raises(AssertionError):
        tiny_request(mode="nope")


def test_response_json_roundtrips():
    svc = make_service()
    try:
        resp = svc.request(tiny_request())
    finally:
        svc.close()
    d = json.loads(resp.to_json())
    assert d["status"] == "ok"
    assert d["best"]["arch_name"] == resp.best["arch_name"]
    assert len(d["frontier_points"]) == len(resp.frontier_points)


# ---------------------------------------------------------------------------
# Journal-as-cache semantics.
# ---------------------------------------------------------------------------

def test_repeat_request_served_from_memo_then_journal(tmp_path):
    path = str(tmp_path / "service.jsonl")
    svc = make_service(journal_path=path)
    try:
        r1 = svc.request(tiny_request())
        assert r1.served_from == "search" and r1.evaluated == 4
        r2 = svc.request(tiny_request())
        assert r2.served_from == "memo"
        assert svc.stats["sweeps"] == 1      # memo answered without a sweep
        assert r2.frontier_json == r1.frontier_json
    finally:
        svc.close()
    # a fresh service on the same journal (restart): zero new searches
    svc2 = make_service(journal_path=path)
    try:
        r3 = svc2.request(tiny_request())
        assert r3.served_from == "journal"
        assert r3.evaluated == 0 and r3.from_journal == 4
        assert r3.frontier_json == r1.frontier_json   # byte-identical
    finally:
        svc2.close()


def test_bigger_budget_request_reuses_smaller_requests_points(tmp_path):
    svc = make_service(journal_path=str(tmp_path / "service.jsonl"))
    try:
        r1 = svc.request(tiny_request(budget=2))
        assert r1.evaluated == 2
        r2 = svc.request(tiny_request(budget=4))
        # grid order is deterministic: the first 2 points come from the
        # journal, only the 2 new ones are searched
        assert r2.from_journal == 2 and r2.evaluated == 2
    finally:
        svc.close()


def test_service_frontier_matches_direct_run_dse(tmp_path):
    svc = make_service(journal_path=str(tmp_path / "service.jsonl"))
    try:
        resp = svc.request(tiny_request())
    finally:
        svc.close()
    res = run_dse(tiny_request().dse_config(), space=tiny_space(),
                  journal=RunJournal())
    assert resp.frontier_json == res.frontier.canonical_json()


# ---------------------------------------------------------------------------
# Coalescing.
# ---------------------------------------------------------------------------

def test_concurrent_identical_requests_share_one_sweep():
    svc = make_service(max_workers=1)
    gate = threading.Event()
    blocker, _ = svc._queue.submit("blocker", gate.wait)
    try:
        req = tiny_request()
        j1 = svc.submit(req)       # queued behind the blocker
        j2 = svc.submit(req)       # identical + in flight => coalesced
        assert j2 is j1
        assert j1.n_attached == 2
        assert svc.stats["coalesced"] == 1
        gate.set()
        r1, r2 = j1.result(60), j2.result(60)
        assert r1 is r2
        assert svc.stats["sweeps"] == 1
        # after completion: answered by the memo, still one sweep
        r3 = svc.request(req)
        assert r3.served_from == "memo" and svc.stats["sweeps"] == 1
    finally:
        gate.set()
        blocker.result(60)
        svc.close()


def test_different_requests_do_not_coalesce():
    svc = make_service(max_workers=1)
    try:
        j1 = svc.submit(tiny_request(seed=0))
        j2 = svc.submit(tiny_request(seed=1))
        assert j1 is not j2
        j1.result(60), j2.result(60)
        assert svc.stats["sweeps"] == 2 and svc.stats["coalesced"] == 0
    finally:
        svc.close()


def test_job_queue_propagates_errors_and_tracks_inflight():
    q = JobQueue(max_workers=1)
    try:
        def boom():
            raise RuntimeError("no")
        job, coalesced = q.submit("k", boom)
        assert not coalesced
        with pytest.raises(RuntimeError, match="no"):
            job.result(10)
        assert job.status == "failed"
        # the key left the in-flight table: a resubmit runs fresh
        ok, coalesced = q.submit("k", lambda: 42)
        assert not coalesced
        assert ok is not job and ok.result(10) == 42
        assert q.inflight() == 0
        assert Job.completed("m", 7).result(0) == 7
    finally:
        q.shutdown()


# ---------------------------------------------------------------------------
# Deadlines (best-so-far answers).
# ---------------------------------------------------------------------------

def test_deadline_returns_best_so_far_and_converges(tmp_path):
    path = str(tmp_path / "service.jsonl")
    svc = make_service(journal_path=path)
    try:
        # deadline 0: the baseline is always scored, nothing more
        r = svc.request(tiny_request(deadline_s=0.0))
        assert r.deadline_hit and r.proposed == 1
        assert r.status == "ok" and r.best is not None
        assert r.best["arch_name"] == r.baseline["arch_name"]
    finally:
        svc.close()
    # warm journal: replaying the prefix is near-free, so repeated
    # deadline requests make monotone progress through the sweep (each
    # one spends its deadline on new points and lands at least one).
    # One LIVE service throughout: deadline-truncated answers must not
    # be memoized, or the service would freeze at the first cut.
    svc = make_service(journal_path=path)
    try:
        seen = 1
        for _ in range(8):
            r = svc.request(tiny_request(deadline_s=0.2))
            assert r.served_from != "memo"
            assert r.proposed >= seen
            seen = r.proposed
            if not r.deadline_hit:
                break
        assert not r.deadline_hit       # converged to the full budget
    finally:
        svc.close()
    # the full request now needs no deadline headroom at all
    svc = make_service(journal_path=path)
    try:
        full = svc.request(tiny_request())
        assert full.evaluated == 0 and full.from_journal == 4
    finally:
        svc.close()


def test_run_dse_deadline_stats_flag():
    res = run_dse(tiny_request().dse_config(), space=tiny_space(),
                  journal=RunJournal())
    assert res.stats["deadline_hit"] is False
    res = run_dse(tiny_request().dse_config(), space=tiny_space(),
                  journal=RunJournal(), deadline_s=0.0)
    assert res.stats["deadline_hit"] is True
    assert len(res.records) >= 1          # the baseline always lands


# ---------------------------------------------------------------------------
# Area budgets and mapping materialization.
# ---------------------------------------------------------------------------

def test_area_budget_constrains_winner():
    svc = make_service()
    try:
        free = svc.request(tiny_request())
        areas = sorted(p["area_mm2"] for p in free.frontier_points)
        cap = areas[0]
        capped = svc.request(tiny_request(area_budget_mm2=cap))
        assert capped.status == "ok"
        assert capped.best["area_mm2"] <= cap + 1e-12
        infeasible = svc.request(tiny_request(area_budget_mm2=cap * 0.01))
        assert infeasible.status == "infeasible"
        assert infeasible.best is None
        assert infeasible.frontier_points    # frontier still reported
    finally:
        svc.close()


def test_area_budget_winner_honors_search_objective():
    """Under an area budget the winner minimizes the *request's*
    objective (here EDP), not unconditionally latency."""
    svc = make_service()
    try:
        free = svc.request(tiny_request(objective="edp"))
        cap = max(p["area_mm2"] for p in free.frontier_points)
        capped = svc.request(tiny_request(objective="edp",
                                          area_budget_mm2=cap))
    finally:
        svc.close()
    # ground truth from a direct sweep: min objective_value in budget
    res = run_dse(tiny_request(objective="edp").dse_config(),
                  space=tiny_space(), journal=RunJournal())
    eligible = [r for r in res.records
                if r["area_mm2"] <= cap + 1e-12]
    want = min(eligible, key=lambda r: r["objective_value"])
    assert capped.best["point_key"] == want["point_key"]
    assert capped.best["objective_value"] == want["objective_value"]


def test_include_mapping_materializes_loop_nests():
    svc = make_service()
    try:
        resp = svc.request(tiny_request(include_mapping=True))
        assert resp.mapping and len(resp.mapping) == resp.best["n_layers"]
        for lay in resp.mapping:
            assert lay["nest"] and isinstance(lay["nest"], str)
            assert lay["latency_ns"] > 0
        total = sum(lay["energy_pj"] for lay in resp.mapping)
        assert total == pytest.approx(resp.best["energy_pj"])
    finally:
        svc.close()


def test_mapping_materialization_cached_per_winner(monkeypatch):
    """The winner's loop nests are searched once and cached by the
    winning record's content key — a second request with a different
    cache key but the same winner replays them without a new search."""
    calls = []
    orig = MappingService._materialize_mapping

    def counting(self, req, best):
        calls.append(best["key"])
        return orig(self, req, best)

    monkeypatch.setattr(MappingService, "_materialize_mapping", counting)
    svc = make_service()
    try:
        r1 = svc.request(tiny_request(include_mapping=True,
                                      deadline_s=300.0))
        assert not r1.deadline_hit and r1.mapping
        # different deadline => different cache key => memo miss, but
        # the journal-served sweep picks the same winner
        r2 = svc.request(tiny_request(include_mapping=True,
                                      deadline_s=301.0))
        assert r2.served_from == "journal"
        assert r2.mapping == r1.mapping
        assert len(calls) == 1
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# Multi-tenant hardening: shared-state races, provenance accounting,
# objective ranking, shared-engine reuse, LRU/persistence, compaction.
# ---------------------------------------------------------------------------

def test_mixed_key_stress_under_concurrency(tmp_path):
    """max_workers=4 with a mix of repeated keys: the shared journal,
    memo, and nest cache are all mutated from concurrent workers, and
    every response must still be correct and byte-identical per key."""
    svc = make_service(journal_path=str(tmp_path / "svc.jsonl"),
                       max_workers=4)
    seeds = [0, 1, 2, 3]
    reqs = [tiny_request(seed=s, include_mapping=True)
            for s in seeds] * 3
    out = [None] * len(reqs)

    def one(i: int) -> None:
        out[i] = svc.request(reqs[i], timeout=600)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    svc.close()
    by_seed = {}
    for r, req in zip(out, reqs):
        assert r is not None and r.status == "ok" and r.mapping
        by_seed.setdefault(req.seed, []).append(r)
    for rs in by_seed.values():
        assert len({r.frontier_json for r in rs}) == 1
        assert len({json.dumps(r.mapping) for r in rs}) == 1
    # ground truth: an independent serial sweep per seed (the shared
    # engine and the concurrency must not perturb any answer)
    for seed in seeds:
        res = run_dse(tiny_request(seed=seed).dse_config(),
                      space=tiny_space(), journal=RunJournal())
        assert by_seed[seed][0].frontier_json \
            == res.frontier.canonical_json()


def test_provenance_counters_sum_to_requests():
    """Every arrival is accounted exactly once: the four served_from
    counters plus the shed counter partition serve.requests."""
    svc = make_service(max_pending=1)
    gate = threading.Event()
    blocker, _ = svc._queue.submit("blocker", lambda: gate.wait(60))
    try:
        while svc._queue.pending() != 0:
            pass
        req = tiny_request()
        j1 = svc.submit(req)              # -> search (fills the 1 slot)
        j2 = svc.submit(req)              # -> coalesced
        assert j2 is j1
        with pytest.raises(QueueFull):
            svc.submit(tiny_request(seed=9))   # -> shed
        gate.set()
        j1.result(120)
        r = svc.request(req)              # -> memo
        assert r.served_from == "memo"
    finally:
        gate.set()
        svc.close()
    c = svc.metrics_snapshot()["counters"]
    total = int(c.get("serve.requests", 0))
    assert total == 4
    provenance = sum(int(c.get(f"serve.served_from.{s}", 0))
                     for s in ("memo", "journal", "search", "coalesced"))
    assert provenance + int(c.get("serve.shed", 0)) == total
    assert svc.stats["shed"] == 1
    # coalesced waiters observe the latency histogram too: one sample
    # per arrival that got an answer (4 arrivals - 1 shed)
    hist = svc.metrics_snapshot()["histograms"]["serve.request_seconds"]
    assert hist["count"] == 3


def test_memo_replay_reports_zero_work():
    svc = make_service()
    try:
        r1 = svc.request(tiny_request())
        r2 = svc.request(tiny_request())
    finally:
        svc.close()
    assert r1.evaluated > 0 and r1.wall_s > 0
    # provenance describes THIS answer: a replay did no sweep work
    assert r2.served_from == "memo"
    assert (r2.evaluated, r2.from_journal, r2.wall_s) == (0, 0, 0.0)
    assert r2.frontier_json == r1.frontier_json
    assert r2.best == r1.best


def test_best_recomputes_objective_from_record_fields():
    """Ranking never trusts a stored objective_value: records missing
    it (pre-energy journal schema) must still rank under the request's
    objective, not silently fall back to latency."""
    svc = make_service()
    try:
        rec_fast = {"total_ns": 100.0, "energy_pj": 1000.0,
                    "area_mm2": 1.0}
        rec_low_edp = {"total_ns": 200.0, "energy_pj": 100.0,
                       "area_mm2": 1.0}
        res = SimpleNamespace(records=[rec_fast, rec_low_edp])
        assert svc._best(tiny_request(objective="edp"), res) \
            is rec_low_edp
        assert svc._best(tiny_request(objective="energy"), res) \
            is rec_low_edp
        assert svc._best(tiny_request(), res) is rec_fast
    finally:
        svc.close()


def test_pre_energy_schema_journal_ranks_correctly(tmp_path):
    """Regression: replay an EDP request against a journal whose
    records were written without objective_value/edp_ns_pj (the
    pre-energy schema) — the winner must match the modern answer."""
    path = str(tmp_path / "svc.jsonl")
    req = tiny_request(objective="edp")
    svc = make_service(journal_path=path)
    try:
        r1 = svc.request(req)
    finally:
        svc.close()
    stripped = []
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            d = json.loads(line)
            d.pop("objective_value", None)
            d.pop("edp_ns_pj", None)
            stripped.append(d)
    with open(path, "w", encoding="utf-8") as fh:
        for d in stripped:
            fh.write(json.dumps(d, sort_keys=True) + "\n")
    svc2 = make_service(journal_path=path)
    try:
        r2 = svc2.request(req)
    finally:
        svc2.close()
    assert r2.served_from == "journal" and r2.evaluated == 0
    assert r2.best["point_key"] == r1.best["point_key"]
    assert r2.frontier_json == r1.frontier_json


def test_shared_engine_warms_perf_cache_across_requests():
    """Two distinct same-family requests (different journal keys, same
    deterministic mapping candidates): the second starts with the
    first's PerfCache and arch bundles warm — nonzero cross-request
    hit rate — without perturbing its answer."""
    svc = make_service()
    try:
        svc.request(tiny_request())                     # latency
        perf = svc._engine._perf
        h1, m1 = perf.hits, perf.misses
        assert m1 > 0
        r2 = svc.request(tiny_request(objective="edp"))  # same family
        h2, m2 = perf.hits, perf.misses
        assert r2.evaluated > 0          # a real sweep, not a replay
        assert h2 > h1                   # warm hits across requests
        assert (m2 - m1) < m1            # far fewer cold analyses
        c = svc.metrics_snapshot()["counters"]
        assert int(c.get("engine.perf_hit", 0)) == h2
        assert int(c.get("engine.perf_miss", 0)) == m2
    finally:
        svc.close()
    # the shared engine is a cache, never an answer-changer
    res = run_dse(tiny_request(objective="edp").dse_config(),
                  space=tiny_space(), journal=RunJournal())
    assert r2.frontier_json == res.frontier.canonical_json()


def test_memo_lru_eviction_backstopped_by_journal():
    svc = make_service(memo_cap=2)
    try:
        svc.request(tiny_request(seed=0))
        svc.request(tiny_request(seed=1))
        svc.request(tiny_request(seed=2))     # evicts seed=0's memo
        r0 = svc.request(tiny_request(seed=0))
        assert r0.served_from == "journal"    # re-ran, all points warm
        assert r0.evaluated == 0
        r2 = svc.request(tiny_request(seed=2))
        assert r2.served_from == "memo"       # still resident
    finally:
        svc.close()


def test_persist_dir_restores_memo_and_nests(tmp_path):
    journal = str(tmp_path / "svc.jsonl")
    persist = str(tmp_path / "persist")
    req = tiny_request(include_mapping=True)
    svc = make_service(journal_path=journal, persist_dir=persist)
    try:
        r1 = svc.request(req)
        assert r1.served_from == "search" and r1.mapping
    finally:
        svc.close()
    # a restarted server answers from the reloaded memo: zero sweeps
    svc2 = make_service(journal_path=journal, persist_dir=persist)
    try:
        r2 = svc2.request(req)
        assert r2.served_from == "memo"
        assert svc2.stats["sweeps"] == 0
        assert r2.frontier_json == r1.frontier_json
        assert r2.mapping == r1.mapping
        # the nest cache came back too: a different-keyed request with
        # the same winner replays the nests without a mapping search
        calls = []
        orig = MappingService._materialize_mapping
        MappingService._materialize_mapping = \
            lambda self, rq, best: calls.append(1) or orig(self, rq, best)
        try:
            r3 = svc2.request(tiny_request(include_mapping=True,
                                           deadline_s=123.0))
        finally:
            MappingService._materialize_mapping = orig
        assert r3.mapping == r1.mapping and calls == []
    finally:
        svc2.close()


def test_compact_rewrites_persisted_caches_and_journal(tmp_path):
    journal = str(tmp_path / "svc.jsonl")
    persist = str(tmp_path / "persist")
    svc = make_service(journal_path=journal, persist_dir=persist,
                       memo_cap=1)
    try:
        svc.request(tiny_request(seed=0))
        svc.request(tiny_request(seed=1))   # evicts seed=0 from memo
        memo_file = str(tmp_path / "persist" / "memo.jsonl")
        with open(memo_file) as fh:
            assert len(fh.read().splitlines()) == 2   # write-through
        svc.compact()
        with open(memo_file) as fh:
            lines = fh.read().splitlines()
        assert len(lines) == 1              # evicted entry dropped
        assert json.loads(lines[0])["key"] \
            == tiny_request(seed=1).cache_key()
        assert svc.metrics_snapshot()["counters"]["serve.compactions"] \
            == 1
    finally:
        svc.close()


def test_background_compaction_cadence(tmp_path):
    svc = make_service(journal_path=str(tmp_path / "svc.jsonl"),
                       persist_dir=str(tmp_path / "persist"),
                       compact_every_s=0.05)
    try:
        svc.request(tiny_request())
        deadline = time.time() + 30
        while time.time() < deadline:
            c = svc.metrics_snapshot()["counters"]
            if c.get("serve.compactions", 0) >= 2:
                break
            time.sleep(0.02)
        assert c.get("serve.compactions", 0) >= 2
    finally:
        svc.close()
    # close() stopped the maintenance thread
    assert svc._compactor is None


def test_response_from_dict_rejects_unknown_fields():
    svc = make_service()
    try:
        resp = svc.request(tiny_request())
    finally:
        svc.close()
    again = MappingResponse.from_dict(resp.to_dict())
    assert again == resp
    bad = resp.to_dict()
    bad["extra"] = 1
    with pytest.raises(ValueError, match="extra"):
        MappingResponse.from_dict(bad)


# ---------------------------------------------------------------------------
# Flight recorder + sliding windows: stage accounting, slow retention,
# scrape-time gauges, and the determinism pin.
# ---------------------------------------------------------------------------

def test_flight_records_every_request_path():
    """memo / search / coalesced / shed all leave a flight record with
    the right provenance, and fresh-job stage timings satisfy the
    identity admit + evaluate + respond == total."""
    svc = make_service(max_pending=1)
    gate = threading.Event()
    blocker, _ = svc._queue.submit("blocker", lambda: gate.wait(60))
    try:
        while svc._queue.pending() != 0:
            pass
        req = tiny_request()
        j1 = svc.submit(req)                       # -> search
        j2 = svc.submit(req)                       # -> coalesced
        assert j2 is j1
        with pytest.raises(QueueFull):
            svc.submit(tiny_request(seed=9))       # -> shed
        gate.set()
        j1.result(120)
        svc.request(req)                           # -> memo
    finally:
        gate.set()
        svc.close()
    recs = svc.flight.snapshot()
    by_src = {r["served_from"]: r for r in recs}
    assert set(by_src) == {"search", "coalesced", "shed", "memo"}
    assert by_src["shed"]["outcome"] == "shed"
    search = by_src["search"]
    assert search["outcome"] == "ok" and search["evaluated"] == 4
    for stage in ("admit_wait_s", "evaluate_s", "respond_s"):
        assert search[stage] >= 0.0
    assert search["admit_wait_s"] + search["evaluate_s"] \
        + search["respond_s"] == pytest.approx(search["total_s"])
    # the blocker held the single worker: the search request's admit
    # wait is real, not epsilon
    assert search["admit_wait_s"] > 0.0
    # memo/coalesced did no evaluate work
    assert by_src["memo"]["evaluate_s"] == 0.0
    assert by_src["coalesced"]["evaluate_s"] == 0.0
    json.dumps(recs)                               # JSON-safe


def test_flight_records_engine_lock_wait():
    """A request whose sweep queues behind a held engine lock records
    the wait as ``lock_wait_s``, inside its ``evaluate_s``; memo
    replays record none."""
    svc = make_service()
    held = 0.2
    try:
        with svc._engine_lock:
            job = svc.submit(tiny_request())
            deadline = time.monotonic() + 60
            while job.t_eval_start is None and time.monotonic() < deadline:
                time.sleep(0.001)
            assert job.t_eval_start is not None
            time.sleep(held)
        job.result(120)
        svc.request(tiny_request())                # -> memo
    finally:
        svc.close()
    by_src = {r["served_from"]: r for r in svc.flight.snapshot()}
    search = by_src["search"]
    assert held * 0.5 < search["lock_wait_s"] <= search["evaluate_s"]
    assert by_src["memo"]["lock_wait_s"] == 0.0


def test_flight_stage_sum_matches_request_seconds_histogram():
    """Acceptance: a fresh request's admit_wait + evaluate equals the
    serve.request_seconds observation for it, up to the respond-stage
    epsilon (the histogram observes at the end of the evaluate stage;
    t_finish lands after the respond hop)."""
    svc = make_service()
    try:
        svc.request(tiny_request())
    finally:
        svc.close()
    [rec] = [r for r in svc.flight.snapshot()
             if r["served_from"] == "search"]
    hist = svc.metrics_snapshot()["histograms"]["serve.request_seconds"]
    assert hist["count"] == 1
    stage_sum = rec["admit_wait_s"] + rec["evaluate_s"]
    # observed value == sum of observations for a single request
    assert abs(hist["sum"] - stage_sum) \
        <= rec["respond_s"] + 0.05 * hist["sum"] + 0.005


def test_flight_slow_request_keeps_full_detail():
    """slow_threshold_s=0 marks every request slow: the slow ring keeps
    the request dict, sweep summary and the engine stats delta."""
    svc = make_service(slow_threshold_s=0.0)
    try:
        r1 = svc.request(tiny_request())
    finally:
        svc.close()
    full = svc.flight.get(r1.request_key[:10])   # prefix lookup
    assert full is not None and full["slow"]
    assert full["request"]["network"] == "resnet18"
    assert full["summary"] and full["frontier_size"] \
        == len(r1.frontier_points)
    delta = full["engine_delta"]
    assert delta and all(isinstance(v, int) for v in delta.values())
    assert delta.get("score_miss", 0) > 0        # the sweep's own work


def test_flight_disabled_and_windows_disabled():
    svc = make_service(flight_cap=0, window_s=0)
    try:
        svc.request(tiny_request())
        snap = svc.metrics_snapshot()
    finally:
        svc.close()
    assert not svc.flight.enabled
    assert "flight" not in snap
    assert "serve.request_seconds.window.p50" not in snap["gauges"]


def test_window_gauges_and_slo_published_at_scrape():
    svc = make_service(slo_target_s=0.001)   # everything breaches
    try:
        svc.request(tiny_request())
        svc.request(tiny_request())          # memo: sub-ms, ok
        snap = svc.metrics_snapshot()
    finally:
        svc.close()
    g, c = snap["gauges"], snap["counters"]
    assert g["serve.request_seconds.window.count"] == 2.0
    assert g["serve.request_seconds.window.p99"] \
        >= g["serve.request_seconds.window.p50"] >= 0.0
    assert g["serve.slo.target_s"] == pytest.approx(0.001)
    assert int(c["serve.slo.breach"]) == 1   # the real sweep
    assert int(c["serve.slo.ok"]) == 1       # the memo replay
    assert g["serve.slo.burn_rate"] > 0.0
    # the snapshot renders through both surfaces without error
    from repro.obs import render_prometheus, render_report
    assert "flight recorder" in render_report(snap)
    assert "repro_serve_slo_burn_rate" in render_prometheus(snap)


def test_frontier_identical_with_flight_and_windows_toggled(tmp_path):
    """Determinism pin (DESIGN.md Sections 12/14): the flight recorder
    and the windows observe, never steer — the canonical frontier JSON
    is byte-identical with them on, off, or in slow-everything mode."""
    base = make_service(flight_cap=0, window_s=0)
    try:
        r_off = base.request(tiny_request())
    finally:
        base.close()
    on = make_service(flight_cap=8, slow_threshold_s=0.0,
                      window_s=30.0, slo_target_s=0.5)
    try:
        r_on = on.request(tiny_request())
    finally:
        on.close()
    assert r_on.frontier_json == r_off.frontier_json

    def strip_wall(d):
        return {k: v for k, v in d.items() if k != "wall_s"}

    # everything but the (inherently nondeterministic) wall clock
    assert strip_wall(r_on.best) == strip_wall(r_off.best)
    assert [strip_wall(p) for p in r_on.frontier_points] \
        == [strip_wall(p) for p in r_off.frontier_points]
    assert len(on.flight) == 1 and len(base.flight) == 0


def test_jobs_stage_timestamps_are_telemetry_only():
    """The queue stamps t_submit/t_eval_start/t_eval_end/t_finish in
    stage order; a pre-completed job only has t_finish."""
    q = JobQueue(max_workers=1)
    try:
        job, _ = q.submit("k", lambda: 41)
        assert job.result(10) == 41
        while job.t_finish is None:
            time.sleep(0.001)
        assert job.t_submit <= job.t_eval_start <= job.t_eval_end \
            <= job.t_finish
    finally:
        q.shutdown()
    done = Job.completed("m", 7)
    assert done.t_finish is not None and done.t_submit is None


# ---------------------------------------------------------------------------
# Serve LM engine: the fast (non-compiling) sampling paths.
# ---------------------------------------------------------------------------

def _bare_engine(**scfg) -> Engine:
    # _sample needs only the config — skip __init__'s jit/model setup
    eng = object.__new__(Engine)
    eng.scfg = ServeConfig(**scfg)
    return eng


def test_engine_sample_greedy_is_argmax():
    eng = _bare_engine(temperature=0.0)
    logits = np.array([[0.1, 2.0, -1.0], [3.0, 0.0, 1.0]], np.float32)
    out = np.asarray(eng._sample(logits, None))
    np.testing.assert_array_equal(out, [1, 0])
    assert out.dtype == np.int32


def test_engine_sample_temperature_seeded_and_in_vocab():
    import jax
    eng = _bare_engine(temperature=0.7)
    logits = np.array([[0.5, 1.5, 0.0, -2.0]] * 8, np.float32)
    a = np.asarray(eng._sample(logits, jax.random.PRNGKey(0)))
    b = np.asarray(eng._sample(logits, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(a, b)      # deterministic in the key
    assert ((a >= 0) & (a < 4)).all()
    # low temperature concentrates on the argmax
    cold = np.asarray(_bare_engine(temperature=1e-4)._sample(
        logits, jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(cold, np.ones_like(cold))
