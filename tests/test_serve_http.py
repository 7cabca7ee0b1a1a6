"""HTTP transport tests: one wire schema, determinism over the socket,
and the 400/404/429 error surface.

Each test binds a real ``MappingHTTPServer`` on an ephemeral loopback
port and drives it with ``urllib`` — the same stack the CI smoke leg
uses — over the restricted space of
``test_serve_service.py`` so everything stays in the fast core loop.
"""
import json
import threading
import urllib.error
import urllib.request

import pytest

from repro.serve import MappingHTTPServer, MappingResponse

from test_serve_service import make_service, tiny_request


def _post(url, body, timeout=60.0):
    data = body if isinstance(body, bytes) else json.dumps(body).encode()
    r = urllib.request.Request(
        url + "/v1/mapping", data=data,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get(url, path, timeout=10.0):
    with urllib.request.urlopen(url + path, timeout=timeout) as resp:
        return resp.status, resp.read()


@pytest.fixture()
def server():
    srv = MappingHTTPServer(make_service(), port=0).start()
    yield srv
    srv.close()


def test_post_mapping_roundtrip(server):
    req = tiny_request()
    code, body = _post(server.url, req.to_dict())
    assert code == 200
    resp = MappingResponse.from_dict(body)
    assert resp.status == "ok"
    assert resp.request_key == req.cache_key()
    assert resp.served_from == "search"
    assert resp.evaluated > 0
    assert resp.best is not None
    # the wire response is the service's canonical serialization
    assert body == json.loads(resp.to_json())


def test_repeat_request_is_memo_with_byte_identical_frontier(server):
    req = tiny_request().to_dict()
    _, first = _post(server.url, req)
    _, second = _post(server.url, req)
    assert second["served_from"] == "memo"
    # provenance counts the work done for THIS answer: none
    assert second["evaluated"] == 0
    assert second["from_journal"] == 0
    assert second["wall_s"] == 0.0
    # the payload itself is byte-identical — THE determinism artifact
    assert second["frontier_json"].encode() \
        == first["frontier_json"].encode()
    assert second["best"] == first["best"]
    assert second["frontier_points"] == first["frontier_points"]


def test_bad_json_and_bad_fields_are_400(server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(server.url, b"{not json")
    assert ei.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(server.url, {"network": "resnet18", "objectiv": "edp"})
    assert ei.value.code == 400
    assert "objectiv" in json.loads(ei.value.read())["error"]


def test_unknown_routes_are_404(server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server.url, "/v1/nope")
    assert ei.value.code == 404
    r = urllib.request.Request(      # POST to a GET-only route
        server.url + "/v1/healthz", data=b"{}",
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(r, timeout=10.0)
    assert ei.value.code == 404


def test_healthz_and_metrics(server):
    code, body = _get(server.url, "/v1/healthz")
    assert code == 200
    health = json.loads(body)
    assert health["status"] == "ok"
    _post(server.url, tiny_request().to_dict())
    code, text = _get(server.url, "/v1/metrics")
    assert code == 200
    text = text.decode()
    # Prometheus text exposition of the serve counters
    assert "repro_serve_requests_total 1" in text
    assert "repro_serve_served_from_search_total 1" in text
    assert "# TYPE repro_serve_requests_total counter" in text


def test_debug_requests_listing_and_lookup(server):
    """GET /v1/debug/requests mirrors the flight recorder: listing,
    ?limit/?slow filters, and the per-key prefix lookup; the listed
    stage timings satisfy the stage identity."""
    req = tiny_request()
    _post(server.url, req.to_dict())
    _post(server.url, req.to_dict())          # memo replay
    code, body = _get(server.url, "/v1/debug/requests")
    assert code == 200
    d = json.loads(body)
    assert d["count"] == 2
    newest, oldest = d["requests"]
    assert newest["served_from"] == "memo"    # newest first
    assert oldest["served_from"] == "search"
    assert oldest["admit_wait_s"] + oldest["evaluate_s"] \
        + oldest["respond_s"] == pytest.approx(oldest["total_s"])
    # stage sum vs the scraped latency histogram (the acceptance bar:
    # equal up to the respond-stage epsilon); the memo hit contributes
    # only its sub-ms replay
    _, text = _get(server.url, "/v1/metrics")
    line = [ln for ln in text.decode().splitlines()
            if ln.startswith("repro_serve_request_seconds_sum")][0]
    observed = float(line.split()[-1])
    stage_sum = sum(r["admit_wait_s"] + r["evaluate_s"]
                    for r in d["requests"])
    eps = sum(r["respond_s"] for r in d["requests"])
    assert abs(observed - stage_sum) <= eps + 0.05 * observed + 0.005
    # limit + per-key lookup (prefix)
    code, body = _get(server.url, "/v1/debug/requests?limit=1")
    assert json.loads(body)["count"] == 1
    key = req.cache_key()
    code, body = _get(server.url, f"/v1/debug/requests/{key[:10]}")
    assert code == 200
    assert json.loads(body)["key"] == key
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server.url, "/v1/debug/requests/ffffffffffffffff")
    assert ei.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server.url, "/v1/debug/requests?limit=zz")
    assert ei.value.code == 400


def test_debug_requests_slow_ring_over_http():
    svc = make_service(slow_threshold_s=0.0)   # everything is "slow"
    srv = MappingHTTPServer(svc, port=0).start()
    try:
        _post(srv.url, tiny_request().to_dict())
        code, body = _get(srv.url, "/v1/debug/requests?slow=1")
        assert code == 200
        d = json.loads(body)
        assert d["count"] == 1
        full = d["requests"][0]
        assert full["slow"] and full["request"]["network"] == "resnet18"
        assert "engine_delta" in full
    finally:
        srv.close()


def test_debug_requests_404_when_disabled():
    svc = make_service(flight_cap=0)
    srv = MappingHTTPServer(svc, port=0).start()
    try:
        for path in ("/v1/debug/requests", "/v1/debug/requests/abc"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.url, path)
            assert ei.value.code == 404
            assert "disabled" in json.loads(ei.value.read())["error"]
    finally:
        srv.close()


def test_metrics_scrape_includes_window_gauges():
    svc = make_service(slo_target_s=0.001)
    srv = MappingHTTPServer(svc, port=0).start()
    try:
        _post(srv.url, tiny_request().to_dict())
        _, text = _get(srv.url, "/v1/metrics")
        text = text.decode()
        assert "repro_serve_request_seconds_window_p50" in text
        assert "repro_serve_request_seconds_window_p99" in text
        assert "repro_serve_slo_burn_rate" in text
        assert "repro_serve_slo_breach_total 1" in text
    finally:
        srv.close()


def test_shed_is_429_with_retry_after():
    gate = threading.Event()
    svc = make_service(max_pending=1)
    srv = MappingHTTPServer(svc, port=0).start()
    try:
        # hold the single worker, then fill the one admission slot, so
        # the next distinct request is shed deterministically
        svc._queue.submit("blocker", lambda: gate.wait(30))
        while svc._queue.pending() != 0:
            pass
        svc._queue.submit("filler", lambda: None)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.url, tiny_request(seed=7).to_dict())
        assert ei.value.code == 429
        assert ei.value.headers["Retry-After"] is not None
        assert svc.stats["shed"] == 1
    finally:
        gate.set()
        srv.close()
