"""Median client-side latency of every request of the window, each
timed from when it was due to be sent; a failed request counts as
infinitely late."""
import statistics


def read(run):
    lat = [r["latency_s"] if r["code"] == 200 else float("inf")
           for r in run.get("requests", [])]
    if not lat:
        return None
    return 1e3 * statistics.median(lat)
