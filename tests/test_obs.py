"""Telemetry subsystem tests (repro.obs) and its determinism contract.

Three layers:

* **Unit** — registry counters/gauges/histograms, snapshot merging,
  quantile interpolation, Prometheus exposition, the JSONL trace sink,
  counter-based span sampling, and the report renderer.
* **Hot-path guard** — the engine's sustained scoring loop must make
  *zero* dispatches into ``repro.obs`` (stats are plain dict ints,
  published as deltas at search end), so telemetry can never tax the
  inner loop; a loose wall-clock ratio backs the structural check.
* **Determinism** — DESIGN.md Section 12: telemetry observes, never
  steers. The engine must match the pre-engine reference, and a
  distributed sweep its serial twin, *byte-identically* with tracing
  enabled (including sampled), and a sweep's canonical frontier JSON
  must not change when telemetry is toggled.
"""
import json

import pytest

from repro import obs
from repro.core import (Edge, FullMap, LayerSpec, SearchConfig,
                        chain_edges, describe, dram_pim, optimize_network,
                        optimize_network_reference)
from repro.core.engine import OverlapEngine, optimize_network_engine
from repro.core.search import _consumers_of, _score_forward, candidates
from repro.dse import (DSEConfig, DistribConfig, ParamSpace,
                       run_distributed, run_dse)
from repro.obs import (Registry, TraceSink, merge_snapshots, quantile,
                       render_prometheus, render_report)
from repro.obs.metrics import DEFAULT_BOUNDS
from repro.obs.trace import _NOOP_SPAN


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with telemetry disabled — the
    process-global switch must never leak across tests."""
    obs.disable()
    yield
    obs.disable()


@pytest.fixture
def tiny_net(monkeypatch):
    """Patch the network lookup everywhere evaluations happen (same
    scheme as tests/test_dse_distrib.py)."""
    import repro.dse.explore as ex

    layers = [
        LayerSpec("l0", K=8, C=4, P=8, Q=8, R=3, S=3, pad=1),
        LayerSpec("l1", K=8, C=8, P=8, Q=8, R=3, S=3, pad=1),
    ]
    desc = type("D", (), {"layers": layers,
                          "edges": chain_edges(layers)})()
    monkeypatch.setattr(ex, "describe", lambda name: desc)


def tiny_space() -> ParamSpace:
    return ParamSpace(
        family="dram_pim",
        axes={
            "channels_per_layer": (1, 2),
            "banks_per_channel": (2, 4),
            "columns_per_bank": (64, 128),
        },
        defaults={"channels_per_layer": 2, "banks_per_channel": 2,
                  "columns_per_bank": 64},
    )


def tiny_dcfg(**kw) -> DSEConfig:
    base = dict(network="tiny", mode="transform", budget=6,
                n_candidates=3, max_steps=256, seed=0, explorer="evolve",
                population=3)
    base.update(kw)
    return DSEConfig(**base)


# ---------------------------------------------------------------------------
# Registry / metrics units.
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = Registry()
    reg.counter("a").inc()
    reg.counter("a").inc(2.5)
    reg.gauge("g").set(7)
    h = reg.histogram("h")
    for v in (1e-6, 1e-3, 1.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3.5
    assert snap["gauges"]["g"] == 7.0
    assert snap["histograms"]["h"]["count"] == 3
    assert snap["histograms"]["h"]["sum"] == pytest.approx(1.001001)
    assert sum(snap["histograms"]["h"]["counts"]) == 3
    # get-or-create returns the same object
    assert reg.counter("a") is reg.counter("a")
    # snapshots are JSON-safe
    json.dumps(snap)


def test_histogram_bounds_mismatch_raises():
    reg = Registry()
    reg.histogram("h", bounds=(1.0, 2.0))
    reg.histogram("h", bounds=(1.0, 2.0))   # same bounds: fine
    with pytest.raises(ValueError):
        reg.histogram("h", bounds=(1.0, 3.0))


def test_quantile_interpolation_and_edges():
    assert quantile((1.0, 2.0), [0, 0, 0], 0.5) == 0.0       # empty
    # 10 observations uniform in the (1, 2] bucket
    assert quantile((1.0, 2.0), [0, 10, 0], 0.5) == pytest.approx(1.5)
    # first bucket interpolates down to 0.0
    assert quantile((1.0, 2.0), [10, 0, 0], 0.5) == pytest.approx(0.5)
    # overflow mass reports the top bound
    assert quantile((1.0, 2.0), [0, 0, 5], 0.99) == 2.0
    # default bounds cover the microsecond..minute range
    assert DEFAULT_BOUNDS[0] <= 1e-6 and DEFAULT_BOUNDS[-1] >= 100.0


def test_merge_snapshots_counters_add_gauges_max_hists_add():
    a, b = Registry(), Registry()
    a.counter("c").inc(2)
    b.counter("c").inc(3)
    a.gauge("g").set(5)
    b.gauge("g").set(2)
    a.histogram("h").observe(0.5)
    b.histogram("h").observe(0.5)
    m = merge_snapshots([a.snapshot(), b.snapshot(), {}])
    assert m["counters"]["c"] == 5.0
    assert m["gauges"]["g"] == 5.0
    assert m["histograms"]["h"]["count"] == 2
    assert m["histograms"]["h"]["sum"] == pytest.approx(1.0)


def test_render_prometheus_shape():
    reg = Registry()
    reg.counter("dse.evaluated").inc(4)
    reg.gauge("serve.queue.depth").set(1)
    reg.histogram("h", bounds=(1.0, 2.0)).observe(1.5)
    text = render_prometheus(reg.snapshot())
    assert "# TYPE repro_dse_evaluated_total counter" in text
    assert "repro_dse_evaluated_total 4" in text
    assert "repro_serve_queue_depth 1" in text
    assert 'repro_h_bucket{le="2"} 1' in text
    assert 'repro_h_bucket{le="+Inf"} 1' in text
    assert "repro_h_count 1" in text
    assert render_prometheus({}) == ""


def test_render_prometheus_labels_and_escaping():
    """Prometheus text-exposition conformance: constant labels reach
    every series (histogram buckets merge them with ``le``), and label
    values escape backslash, double-quote and newline per the format
    spec."""
    from repro.obs import escape_label_value

    assert escape_label_value('a"b') == 'a\\"b'
    assert escape_label_value("a\\b") == "a\\\\b"
    assert escape_label_value("a\nb") == "a\\nb"
    reg = Registry()
    reg.counter("c").inc(1)
    reg.gauge("g").set(2)
    reg.histogram("h", bounds=(1.0,)).observe(0.5)
    text = render_prometheus(reg.snapshot(),
                             labels={"net": 'res"net\n', "w": "a\\b"})
    assert 'repro_c_total{net="res\\"net\\n",w="a\\\\b"} 1' in text
    assert 'repro_g{net="res\\"net\\n",w="a\\\\b"} 2' in text
    # bucket lines merge the constant labels with le=
    assert 'le="1"' in text and 'net="res\\"net\\n"' in text
    for line in text.splitlines():
        if "_bucket" in line and "+Inf" not in line:
            assert line.startswith('repro_h_bucket{')
            assert 'le="1"' in line
    # no labels: unchanged legacy shape
    plain = render_prometheus(reg.snapshot())
    assert "repro_c_total 1" in plain


def test_trace_sink_concurrent_writes_no_torn_lines(tmp_path):
    """N threads hammering one TraceSink must produce valid JSONL —
    every line parses and every event arrives exactly once."""
    import threading

    path = str(tmp_path / "t.jsonl")
    sink = TraceSink(path)
    n_threads, n_events = 8, 200

    def writer(tid):
        for i in range(n_events):
            sink.write({"ev": "event", "tid_": tid, "i": i,
                        "pad": "x" * 100})

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sink.close()
    evs = _read_events(path)      # json.loads raises on a torn line
    assert len(evs) == n_threads * n_events
    seen = {(e["tid_"], e["i"]) for e in evs}
    assert len(seen) == n_threads * n_events


def test_render_report_sections():
    assert render_report({}) == "(no metrics recorded)\n"
    reg = Registry()
    reg.counter("engine.tail_hit").inc(3)
    reg.counter("engine.tail_miss").inc(1)
    reg.counter("dse.evaluated").inc(2)
    reg.histogram("serve.request_seconds").observe(0.25)
    reg.counter("serve.requests").inc(1)
    text = render_report(reg.snapshot())
    assert "hit rate" in text and "75.0%" in text
    assert "dse" in text and "serve" in text


# ---------------------------------------------------------------------------
# Tracing: JSONL sink, nesting, sampling, global switch.
# ---------------------------------------------------------------------------

def _read_events(path):
    with open(path, "r", encoding="utf-8") as fh:
        return [json.loads(line) for line in fh]


def test_span_jsonl_nesting_and_events(tmp_path):
    trace = str(tmp_path / "trace.jsonl")
    obs.enable(trace_path=trace)
    with obs.span("outer", a=1):
        with obs.span("inner"):
            pass
        obs.event("mark", x="y")
    obs.disable()
    evs = _read_events(trace)
    by_name = {e["name"]: e for e in evs}
    assert by_name["inner"]["depth"] == 1
    assert by_name["outer"]["depth"] == 0
    assert by_name["outer"]["a"] == 1
    assert by_name["outer"]["dur_s"] >= by_name["inner"]["dur_s"] >= 0
    assert by_name["mark"]["ev"] == "event" and by_name["mark"]["x"] == "y"
    # spans also feed span.<name> duration histograms
    snap = obs.current().registry if obs.enabled() else None
    assert snap is None                       # disabled again


def test_span_sampling_is_counter_based(tmp_path):
    trace = str(tmp_path / "trace.jsonl")
    obs.enable(trace_path=trace, sample_every=3)
    for _ in range(7):
        with obs.span("s"):
            pass
    obs.disable()
    evs = _read_events(trace)
    assert len(evs) == 3      # spans 0, 3 and 6 of 7 survive the stride
    # metrics are never sampled: only emitted spans hit the histogram,
    # but plain counters always count
    obs.enable()
    for _ in range(7):
        obs.inc("c")
    assert obs.registry().snapshot()["counters"]["c"] == 7.0


def test_disabled_is_total_noop(tmp_path):
    assert not obs.enabled()
    assert obs.registry() is None
    obs.inc("x")
    obs.observe("y", 1.0)
    obs.set_gauge("z", 1.0)
    obs.event("e")
    with obs.span("s", k=1):
        pass                   # shared no-op span, nothing written
    assert obs.registry() is None


def test_metrics_without_sink():
    obs.enable()               # registry only
    assert obs.enabled() and obs.registry() is not None
    with obs.span("s"):        # no sink: no-op span, no histogram
        pass
    obs.inc("c", 2)
    snap = obs.registry().snapshot()
    assert snap["counters"]["c"] == 2.0
    assert "span.s" not in snap["histograms"]


def test_trace_sink_buffers_until_close_or_bound(tmp_path):
    """Spans stay in memory: nothing reaches the file before ``close``
    or before ``FLUSH_LINES`` lines have piled up, and then all of
    them do, in order."""
    import os

    path = str(tmp_path / "t.jsonl")
    sink = TraceSink(path)
    sink.FLUSH_LINES = 4
    for i in range(3):
        sink.write({"i": i})
    assert not os.path.exists(path)
    sink.write({"i": 3})                      # the bound: all four out
    assert [e["i"] for e in _read_events(path)] == [0, 1, 2, 3]
    sink.write({"i": 4})
    assert len(_read_events(path)) == 4
    sink.close()
    assert [e["i"] for e in _read_events(path)] == [0, 1, 2, 3, 4]


def test_trace_sink_reopens_after_close(tmp_path):
    path = str(tmp_path / "t.jsonl")
    sink = TraceSink(path)
    sink.write({"a": 1})
    sink.close()
    sink.write({"b": 2})
    sink.close()
    assert len(_read_events(path)) == 2


# ---------------------------------------------------------------------------
# Flight recorder: bounded rings, slow-request retention, lookup.
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_bounds_and_slow_retention():
    from repro.obs import FlightRecorder

    fr = FlightRecorder(cap=3, slow_threshold_s=0.5, slow_cap=2)
    assert fr.enabled and len(fr) == 0
    for i in range(5):
        fr.record({"key": f"k{i}", "total_s": 0.1})
    assert len(fr) == 3                        # ring evicted the oldest
    snap = fr.snapshot()
    assert [r["key"] for r in snap] == ["k4", "k3", "k2"]  # newest first
    assert all(not r["slow"] for r in snap)
    assert snap[0]["seq"] == 5                 # monotone sequence
    # slow records keep full detail in the separate ring
    fr.record({"key": "slow1", "total_s": 0.9},
              detail={"request": {"network": "resnet18"}})
    assert fr.snapshot()[0]["slow"]
    slow = fr.snapshot(slow_only=True)
    assert len(slow) == 1
    assert slow[0]["request"] == {"network": "resnet18"}
    # ...and survive main-ring rotation
    for i in range(10):
        fr.record({"key": f"x{i}", "total_s": 0.0})
    assert fr.get("slow1")["request"] == {"network": "resnet18"}
    # prefix match; unknown and empty keys are None
    assert fr.get("slo")["key"] == "slow1"
    assert fr.get("nope") is None and fr.get("") is None
    # snapshot limit
    assert len(fr.snapshot(limit=2)) == 2
    json.dumps(fr.snapshot())


def test_flight_recorder_cap_zero_is_noop():
    from repro.obs import FlightRecorder

    fr = FlightRecorder(cap=0)
    assert not fr.enabled
    fr.record({"key": "k", "total_s": 99.0})
    assert len(fr) == 0 and fr.snapshot() == [] and fr.get("k") is None


# ---------------------------------------------------------------------------
# Sliding windows: recent quantiles, aging, SLO burn rate.
# ---------------------------------------------------------------------------

class _FakeClock:
    """Deterministic monotonic clock for window tests."""

    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def test_window_histogram_quantiles_and_aging():
    from repro.obs import WindowHistogram

    clk = _FakeClock()
    w = WindowHistogram(window_s=60.0, n_slots=12, clock=clk)
    assert w.count() == 0 and w.quantile(0.5) == 0.0
    for v in (0.010, 0.011, 0.012, 0.013):
        w.observe(v)
    assert w.count() == 4
    assert w.quantile(0.5) == pytest.approx(0.012, rel=0.5)
    assert w.mean() == pytest.approx(0.0115)
    # half a window later the old slots are still live...
    clk.t += 30.0
    w.observe(0.5)
    assert w.count() == 5
    # ...a full window after the first batch, only the new one remains
    clk.t += 31.0
    assert w.count() == 1
    assert w.quantile(0.99) == pytest.approx(0.5, rel=0.5)
    # and past that, the window is empty again
    clk.t += 61.0
    assert w.count() == 0 and w.quantile(0.5) == 0.0
    snap = w.snapshot()
    assert snap["count"] == 0 and sum(snap["counts"]) == 0
    json.dumps(snap)


def test_slo_tracker_burn_rate():
    from repro.obs import SLOTracker

    clk = _FakeClock()
    slo = SLOTracker(target_s=0.1, goal=0.9, window_s=60.0, clock=clk)
    assert slo.burn_rate() == 0.0               # empty window
    for _ in range(9):
        slo.observe(0.05)                       # ok
    slo.observe(0.5)                            # breach
    assert slo.n_ok == 9 and slo.n_breach == 1
    # 10% breaches against a 10% error budget: burning exactly at 1.0
    assert slo.window_breach_rate() == pytest.approx(0.1)
    assert slo.burn_rate() == pytest.approx(1.0)
    snap = slo.snapshot()
    assert snap["ok"] == 9 and snap["breach"] == 1
    json.dumps(snap)
    # the windowed rate ages out; the all-time counters do not
    clk.t += 120.0
    assert slo.burn_rate() == 0.0
    assert slo.n_breach == 1


# ---------------------------------------------------------------------------
# Engine publication: delta semantics, zero hot-path dispatch.
# ---------------------------------------------------------------------------

def _small_arch():
    return dram_pim(channels_per_layer=2, banks_per_channel=2,
                    columns_per_bank=64)


def _conv_chain():
    return [
        LayerSpec("l0", K=8, C=4, P=8, Q=8, R=3, S=3, pad=1),
        LayerSpec("l1", K=8, C=8, P=8, Q=8, R=3, S=3, pad=1),
        LayerSpec("l2", K=16, C=8, P=4, Q=4, R=3, S=3, stride=2, pad=1),
    ]


def _sustained_setup(n_candidates=8):
    layers = _conv_chain()
    edges = chain_edges(layers)
    arch = _small_arch()
    cfg = SearchConfig(n_candidates=n_candidates, seed=0, max_steps=512,
                       mode="transform")
    res = optimize_network(layers, edges, arch, cfg)
    done = {i: lr for i, lr in enumerate(res.layers)}
    scored = [(i, candidates(layers[i], arch, cfg, salt=i),
               bool(_consumers_of(edges, i)))
              for i in range(len(layers)) if edges[i]]
    return edges, done, scored


def test_publish_metrics_publishes_deltas_once():
    eng = OverlapEngine()
    edges, done, scored = _sustained_setup()
    for i, pool, has_cons in scored:
        eng.score_forward_batch(i, pool, edges, done, "transform",
                                has_cons)
    reg = Registry()
    eng.publish_metrics(registry=reg)
    first = reg.snapshot()["counters"]
    assert first.get("engine.score_miss", 0) > 0
    # publishing again without new work adds nothing (delta semantics)
    eng.publish_metrics(registry=reg)
    assert reg.snapshot()["counters"] == first
    # with telemetry disabled and no explicit registry: a silent no-op
    eng.publish_metrics()


def _mixed_net():
    """An identity edge (l0 -> l1, the batched scorer), a FullMap edge
    (l1 -> l2: the closed form in transform mode, the dense fallback in
    overlap mode) and an identity edge beside a FullMap edge (l1, l2 ->
    l3, the dense fallback in both)."""
    layers = _conv_chain() + [
        LayerSpec("l3", K=8, C=8, P=8, Q=8, R=3, S=3, pad=1)]
    edges = [[], [Edge(0)], [Edge(1, FullMap())],
             [Edge(1), Edge(2, FullMap())]]
    return layers, edges


@pytest.mark.parametrize("mode", ["overlap", "transform"])
def test_full_map_ready_steps_take_the_closed_form(mode):
    """The FullMap layer's ready steps come from the producer alone: in
    overlap mode ``ready_full`` grows per candidate, in transform mode
    the whole pool is scored from one ready constant (``full_scored``);
    either way no consumer tile is projected, the scores are the
    reference's, and the count is published under ``engine.``. An
    identity-only chain never takes it."""
    layers, edges = _mixed_net()
    arch = _small_arch()
    cfg = SearchConfig(n_candidates=8, seed=0, max_steps=512, mode=mode)
    done = dict(enumerate(optimize_network_reference(layers, edges, arch,
                                                     cfg).layers))
    eng = OverlapEngine()
    eng.score_forward_batch(1, candidates(layers[1], arch, cfg, salt=1),
                            edges, done, mode)
    assert eng.stats["ready_full"] == 0
    proj_miss = eng.stats["proj_miss"]
    pool = candidates(layers[2], arch, cfg, salt=2)
    got = eng.score_forward_batch(2, pool, edges, done, mode, False)
    if mode == "overlap":
        assert eng.stats["ready_full"] == len({m.cache_key for m in pool})
        key = "ready_full"
    else:
        assert eng.stats["full_scored"] == len(pool)
        key = "full_scored"
    assert eng.stats["proj_miss"] == proj_miss
    assert list(got) == [_score_forward(2, m, edges, done, mode, False)
                         for m in pool]
    reg = Registry()
    eng.publish_metrics(registry=reg)
    assert reg.snapshot()["counters"]["engine." + key] == eng.stats[key]

    ident = OverlapEngine()
    chain = _conv_chain()
    optimize_network_engine(chain, chain_edges(chain), arch, cfg,
                            engine=ident)
    assert ident.stats["ready_miss"] > 0
    assert ident.stats["ready_full"] == 0
    reg = Registry()
    ident.publish_metrics(registry=reg)
    assert "engine.ready_full" not in reg.snapshot()["counters"]


@pytest.mark.parametrize("network,head_folds", [
    ("granite_moe_1b_a400m_smoke:decode@16", True),
    ("deepseek_v2_smoke_ep2:decode@16x2", True),
    ("resnet18", False)])
def test_ready_cmap_counts_generic_map_ready_matrices(network, head_folds):
    """``engine.ready_cmap``/``ready_cmap_s`` count and time the ready
    matrices computed through generic coordinate maps (head folds,
    weight maps): non-zero on networks with such edges, zero on an
    identity-only network, and at most one per ready-cache miss."""
    desc = describe(network)
    eng = OverlapEngine()
    cfg = SearchConfig(n_candidates=4, seed=0, max_steps=256,
                       mode="transform")
    optimize_network_engine(desc.layers, desc.edges, _small_arch(), cfg,
                            engine=eng)
    assert (eng.stats["ready_cmap"] > 0) is head_folds
    assert (eng.times["ready_cmap_s"] > 0) is head_folds
    assert eng.stats["ready_cmap"] <= eng.stats["ready_miss"]
    reg = Registry()
    eng.publish_metrics(registry=reg)
    got = reg.snapshot()["counters"]
    assert got.get("engine.ready_cmap", 0) == eng.stats["ready_cmap"]
    assert got.get("engine.ready_cmap_s", 0) == eng.times["ready_cmap_s"]


@pytest.mark.parametrize("network,full_layers", [
    ("granite_moe_1b_a400m_smoke:decode@16", True),
    ("deepseek_v2_smoke_ep2:decode@16x2", True),
    ("resnet18", False),
    ("conv_chain", False)])
def test_full_scored_counts_closed_form_pools(network, full_layers):
    """``engine.full_scored``/``score_full_s`` count and time the
    candidates of all-FullMap layers scored in closed form: non-zero on
    MoE decode networks (expert, router and cache-append layers), zero
    on identity-only networks, whose first edge already fails the
    test. The closed-form scores count among ``batch_scored``."""
    if network == "conv_chain":
        layers = _conv_chain()
        edges = chain_edges(layers)
    else:
        desc = describe(network)
        layers, edges = desc.layers, desc.edges
    eng = OverlapEngine()
    cfg = SearchConfig(n_candidates=4, seed=0, max_steps=256,
                       mode="transform")
    optimize_network_engine(layers, edges, _small_arch(), cfg, engine=eng)
    assert (eng.stats["full_scored"] > 0) is full_layers
    assert (eng.times["score_full_s"] > 0) is full_layers
    assert eng.stats["full_scored"] <= eng.stats["batch_scored"]
    reg = Registry()
    eng.publish_metrics(registry=reg)
    got = reg.snapshot()["counters"]
    assert got.get("engine.full_scored", 0) == eng.stats["full_scored"]
    assert got.get("engine.score_full_s", 0) == eng.times["score_full_s"]
    assert ("engine.full_scored" in got) is full_layers



@pytest.mark.parametrize("network,mixed_layers", [
    ("nemotron_3_nano_smoke:decode@16x7", True),
    ("deepseek_v2_smoke_ep2:decode@16x2", True),
    ("resnet18", False),
    ("conv_chain", False)])
def test_mixed_scored_counts_dense_layers_of_mixed_edges(network,
                                                         mixed_layers):
    """``engine.mixed_scored``/``score_mixed_s`` count and time the
    dense scoring of layers whose edges mix FullMap with an exact map
    (a Mamba-2 projection reading the MoE combine beside the shared
    expert, a decode score reading the cache append beside its queries):
    a part of ``dense_scored``/``score_dense_s``, zero on networks
    without FullMap edges."""
    if network == "conv_chain":
        layers = _conv_chain()
        edges = chain_edges(layers)
    else:
        desc = describe(network)
        layers, edges = desc.layers, desc.edges
    eng = OverlapEngine()
    cfg = SearchConfig(n_candidates=4, seed=0, max_steps=256,
                       mode="transform")
    optimize_network_engine(layers, edges, _small_arch(), cfg, engine=eng)
    assert (eng.stats["mixed_scored"] > 0) is mixed_layers
    assert (eng.times["score_mixed_s"] > 0) is mixed_layers
    assert eng.stats["mixed_scored"] <= eng.stats["dense_scored"]
    assert eng.times["score_mixed_s"] <= eng.times["score_dense_s"]
    reg = Registry()
    eng.publish_metrics(registry=reg)
    got = reg.snapshot()["counters"]
    assert got.get("engine.mixed_scored", 0) == eng.stats["mixed_scored"]
    assert got.get("engine.score_mixed_s", 0) == eng.times["score_mixed_s"]
    assert ("engine.score_mixed_s" in got) is mixed_layers


@pytest.mark.parametrize("refine_passes", [0, 1])
def test_draw_counters_count_every_pool_draw(refine_passes):
    """``engine.draws`` counts the random draws of every pool the search
    draws (``n_candidates - 1`` per layer, and ``refine_candidates - 1``
    per layer per refine pass); ``engine.draw_tries`` counts the tries
    they took, rejected ones included. Both are published as they stand
    in ``eng.stats``."""
    layers = _conv_chain()
    cfg = SearchConfig(n_candidates=8, seed=0, max_steps=512,
                       mode="transform", refine_passes=refine_passes,
                       refine_candidates=4)
    eng = OverlapEngine()
    optimize_network_engine(layers, chain_edges(layers), _small_arch(),
                            cfg, engine=eng)
    assert eng.stats["draws"] == len(layers) * (
        cfg.n_candidates - 1 + refine_passes * (cfg.refine_candidates - 1))
    assert eng.stats["draw_tries"] >= eng.stats["draws"] > 0
    reg = Registry()
    eng.publish_metrics(registry=reg)
    got = reg.snapshot()["counters"]
    assert got["engine.draws"] == eng.stats["draws"]
    assert got["engine.draw_tries"] == eng.stats["draw_tries"]

def test_publish_metrics_times_follow_their_counts():
    """``engine.score_batch_s``/``score_full_s``/``score_dense_s`` are
    published once per delta, and each is positive exactly when its
    count grew since the last publish: ``batch_scored`` less
    ``full_scored`` (the closed-form scores count as batched),
    ``full_scored`` and ``dense_scored``."""
    layers, edges = _mixed_net()
    arch = _small_arch()
    cfg = SearchConfig(n_candidates=8, seed=0, max_steps=512,
                       mode="transform")
    res = optimize_network(layers, edges, arch, cfg)
    done = {i: lr for i, lr in enumerate(res.layers)}
    eng = OverlapEngine()
    reg = Registry()

    def score_and_publish(i):
        before = reg.snapshot()["counters"]
        eng.score_forward_batch(i, candidates(layers[i], arch, cfg, salt=i),
                                edges, done, "transform", i < 3)
        eng.publish_metrics(registry=reg)
        after = reg.snapshot()["counters"]
        return {k: after.get(k, 0) - before.get(k, 0) for k in after}

    for i, batch, full, dense in ((1, True, False, False),
                                  (2, False, True, False),
                                  (3, False, False, True)):
        d = score_and_publish(i)
        assert (d.get("engine.batch_scored", 0)
                - d.get("engine.full_scored", 0) > 0) is batch
        assert (d.get("engine.score_batch_s", 0) > 0) is batch
        assert (d.get("engine.full_scored", 0) > 0) is full
        assert (d.get("engine.score_full_s", 0) > 0) is full
        assert (d.get("engine.dense_scored", 0) > 0) is dense
        assert (d.get("engine.score_dense_s", 0) > 0) is dense
    assert all(eng.times[k] > 0 for k in ("score_batch_s", "score_full_s",
                                          "score_dense_s"))
    # the times stay out of the integer stats the service diffs
    assert all(isinstance(v, int) for v in eng.stats.values())
    first = reg.snapshot()["counters"]
    eng.publish_metrics(registry=reg)
    assert reg.snapshot()["counters"] == first


def _traced_search(tmp_path):
    """One traced engine search of the mixed net: (spans by name as
    JSONL events, the published counters)."""
    layers, edges = _mixed_net()
    cfg = SearchConfig(n_candidates=8, seed=0, max_steps=512,
                       mode="transform")
    trace = str(tmp_path / "t.jsonl")
    obs.enable(trace_path=trace)
    optimize_network_engine(layers, edges, _small_arch(), cfg)
    counters = obs.registry().snapshot()["counters"]
    obs.disable()
    spans = {}
    for ev in _read_events(trace):
        spans.setdefault(ev["name"], []).append(ev)
    return spans, counters


def test_search_stage_spans_and_timers_fit_inside_the_layers(tmp_path):
    """One ``search.candidates`` and one ``search.commit`` per layer,
    plus the closing ``search.commit``; the stages and the engine's
    scoring timers together take no more than the layers and the
    closing commit."""
    spans, counters = _traced_search(tmp_path)
    n = len(_mixed_net()[0])
    assert len(spans["search.layer"]) == n
    assert len(spans["search.candidates"]) == n
    commits = spans["search.commit"]
    assert sorted(e.get("layer", -1) for e in commits) \
        == [-1] + list(range(n))
    closing = sum(e["dur_s"] for e in commits if "layer" not in e)
    stages = (sum(e["dur_s"] for e in spans["search.candidates"])
              + sum(e["dur_s"] for e in commits)
              + counters["engine.score_batch_s"]
              + counters["engine.score_full_s"]
              + counters["engine.score_dense_s"])
    assert 0 < stages <= sum(e["dur_s"] for e in spans["search.layer"]) \
        + closing


def test_profiler_host_plane_holds_each_search_span(tmp_path):
    """With JAX loaded, every span is also a ``TraceAnnotation``: the
    profiler's ``/host:`` plane holds one event per JSONL span."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"),
                             profiler_options=opts)
    try:
        spans, _ = _traced_search(tmp_path)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                       recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host[ev.name] = host.get(ev.name, 0) + 1
    for name in ("search.layer", "search.candidates", "search.commit"):
        assert host.get(name) == len(spans[name]), name


def test_sustained_scoring_makes_zero_obs_dispatches(monkeypatch):
    """The structural half of the <5% overhead guarantee: neither the
    cold nor the memo-hit scoring pass may call into ``repro.obs`` at
    all — engine stats are plain dict ints until ``publish_metrics``."""
    eng = OverlapEngine()
    edges, done, scored = _sustained_setup()   # before patching: the
    # setup's own optimize_network legitimately opens search spans
    calls = []
    for fn in ("inc", "observe", "set_gauge", "event", "span"):
        monkeypatch.setattr(obs, fn,
                            lambda *a, _f=fn, **k: calls.append(_f)
                            or _NOOP_SPAN)
    for _ in range(2):          # cold pass, then the sustained regime
        for i, pool, has_cons in scored:
            eng.score_forward_batch(i, pool, edges, done, "transform",
                                    has_cons)
    assert calls == []
    assert eng.stats["score_pool_hit"] > 0      # the memo regime ran


def test_sustained_scoring_overhead_is_bounded():
    """Wall-clock half, deliberately loose (a gross-regression tripwire
    only — a search's tracing cost is read from the benchmark's traced
    runs in ``bench/``): the same sustained pass with telemetry enabled
    must stay within 2x of disabled."""
    import time

    eng = OverlapEngine()
    edges, done, scored = _sustained_setup(n_candidates=16)

    def one_pass():
        t0 = time.perf_counter()
        for _ in range(20):
            for i, pool, has_cons in scored:
                eng.score_forward_batch(i, pool, edges, done,
                                        "transform", has_cons)
        return time.perf_counter() - t0

    one_pass()                  # warm the memo tables
    t_off = min(one_pass() for _ in range(3))
    obs.enable()
    t_on = min(one_pass() for _ in range(3))
    obs.disable()
    assert t_on <= 2.0 * t_off, (t_on, t_off)


# ---------------------------------------------------------------------------
# Fleet shards: worker-local registries merged by the coordinator.
# ---------------------------------------------------------------------------

def test_fleet_shard_write_and_collect(tmp_path):
    from repro.dse.distrib.coordinator import clear_metrics, collect_fleet
    from repro.dse.distrib.worker import write_metrics_shard

    root = str(tmp_path)
    assert collect_fleet(root) is None          # no shards yet
    for wid, n in (("w0", 3), ("w1", 5)):
        reg = Registry()
        reg.counter("fleet.evaluated").inc(n)
        reg.histogram("fleet.batch_eval_seconds").observe(0.1 * n)
        write_metrics_shard(root, wid, {"evaluated": n, "batches": 1},
                            reg)
    fleet = collect_fleet(root)
    assert fleet["summary"]["workers_reported"] == 2
    assert fleet["summary"]["evaluated"] == 8
    assert fleet["summary"]["batches"] == 2
    assert fleet["summary"]["batch_eval_p50_s"] > 0
    snap = fleet["snapshot"]
    assert snap["counters"]["fleet.evaluated"] == 8.0
    assert snap["gauges"]["fleet.workers"] == 2.0
    clear_metrics(root)
    assert collect_fleet(root) is None


# ---------------------------------------------------------------------------
# Determinism: telemetry observes, never steers (DESIGN.md Section 12).
# ---------------------------------------------------------------------------

def test_engine_matches_reference_with_tracing_on(tmp_path):
    layers = _conv_chain()
    edges = chain_edges(layers)
    arch = _small_arch()
    cfg = SearchConfig(n_candidates=8, seed=0, max_steps=512,
                       mode="transform", refine_passes=1)
    ref = optimize_network_reference(layers, edges, arch, cfg)
    obs.enable(trace_path=str(tmp_path / "t.jsonl"), sample_every=2)
    traced = optimize_network(layers, edges, arch, cfg)
    obs.disable()
    untraced = optimize_network(layers, edges, arch, cfg)
    assert traced.total_ns == ref.total_ns == untraced.total_ns
    assert [l.latency_ns for l in traced.layers] \
        == [l.latency_ns for l in ref.layers]


def test_sweep_frontier_identical_with_telemetry_toggled(tiny_net,
                                                         tmp_path):
    base = run_dse(tiny_dcfg(), space=tiny_space())
    obs.enable(trace_path=str(tmp_path / "t.jsonl"))
    traced = run_dse(tiny_dcfg(), space=tiny_space())
    obs.disable()
    sampled = obs.enable(sample_every=4)
    assert sampled.enabled
    resampled = run_dse(tiny_dcfg(), space=tiny_space())
    obs.disable()
    assert traced.frontier.canonical_json() \
        == base.frontier.canonical_json() \
        == resampled.frontier.canonical_json()
    # the traced run actually recorded sweep metrics
    evs = _read_events(str(tmp_path / "t.jsonl"))
    assert any(e["name"] == "dse.sweep" for e in evs)


def test_distributed_matches_serial_with_telemetry_on(tiny_net,
                                                      tmp_path):
    serial = run_dse(tiny_dcfg(), space=tiny_space())
    obs.enable(trace_path=str(tmp_path / "t.jsonl"))
    res = run_distributed(
        tiny_dcfg(), DistribConfig(root=str(tmp_path / "shared"),
                                   n_workers=2, worker_mode="thread"),
        space=tiny_space())
    snap = obs.registry().snapshot()
    obs.disable()
    assert res.frontier.canonical_json() == serial.frontier.canonical_json()
    # the workers' shard metrics were folded into the global registry
    assert snap["counters"]["fleet.evaluated"] == res.stats["evaluated"]
    assert res.stats["fleet"]["workers_reported"] == 2
    assert res.stats["fleet"]["claims"] >= res.stats["fleet"]["batches"]
