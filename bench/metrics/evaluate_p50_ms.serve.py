"""Median ``evaluate_s`` of the window's requests that ran a sweep
(``served_from == "search"``), from the flight recorder."""
import statistics


def read(run):
    e = [r["evaluate_s"] for r in run.get("flight", [])
         if r["served_from"] == "search"]
    if not e:
        return None
    return 1e3 * statistics.median(e)
