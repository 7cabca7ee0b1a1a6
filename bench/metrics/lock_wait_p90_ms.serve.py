"""90th percentile (nearest rank) of the flight recorder's
``lock_wait_s``, the wait for the shared engine's lock inside
``evaluate_s``, over the window's requests that ran a sweep
(``served_from == "search"``)."""
import math


def read(run):
    w = sorted(r["lock_wait_s"] for r in run.get("flight", [])
               if r["served_from"] == "search" and "lock_wait_s" in r)
    if not w:
        return None
    return 1e3 * w[math.ceil(0.9 * len(w)) - 1]
