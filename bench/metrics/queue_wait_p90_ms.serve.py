"""90th percentile (nearest rank) of the flight recorder's
``admit_wait_s`` over the window's requests that went to the queue
(memo replays never wait)."""
import math


def read(run):
    w = sorted(r["admit_wait_s"] for r in run.get("flight", [])
               if r["served_from"] != "memo")
    if not w:
        return None
    return 1e3 * w[math.ceil(0.9 * len(w)) - 1]
