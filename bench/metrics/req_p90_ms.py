"""90th percentile (nearest rank) of the same client-side latencies as
``req_p50_ms``: over every request of the window, failures included as
infinitely late."""
import math


def read(run):
    lat = sorted(r["latency_s"] if r["code"] == 200 else float("inf")
                 for r in run.get("requests", []))
    if not lat:
        return None
    return 1e3 * lat[math.ceil(0.9 * len(lat)) - 1]
