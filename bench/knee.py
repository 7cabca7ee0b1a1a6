"""Sweep the offered rate of an open-loop serving mix, one window each.

    python3 bench/knee.py --workload resnet18.serve-zipf --seed 7 \
        --seconds 30 --rates 0.5,1,1.5,2

For each rate it prints the latency median and 90th percentile, the
completions per second, the sweep worker's busy share ``rho`` (the
flight recorder's summed ``evaluate_s`` over the time from the first
arrival to the last answer) and ``drain_s``, how long the answers ran on
past the window. One sweep worker serves every request that misses the
memo, so the backlog grows once ``rho`` nears 1. The knee is the highest
rate the worker sustains (``rho`` below 0.9); the cell's traffic file
takes about four fifths of it, as a number.
"""
import argparse
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness, program, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    manifest = harness.load_manifest()
    w, c = harness.cell(manifest, args.workload)
    print(harness.check_device(w["chips"]), file=sys.stderr)
    with open(os.path.join(harness.ROOT, c["file"])) as fh:
        dep = program.Deployment(json.load(fh))
    mix = traffic.load(w["traffic"])
    for rate in (float(r) for r in args.rates.split(",")):
        drv = traffic.make(dict(mix, rate=rate), dep, args.seed)
        drv.setup()
        drv.warm()
        t0 = time.perf_counter()
        run = drv.window(args.seconds)
        drain = time.perf_counter() - t0
        drv.release()
        lat = [r["latency_s"] for r in run["requests"]]
        busy = sum(r["evaluate_s"] for r in run["flight"]
                   if r["served_from"] == "search")
        s = sorted(lat)
        print(json.dumps({
            "rate": rate, "requests": len(lat),
            "p50_ms": 1e3 * statistics.median(lat),
            "p90_ms": 1e3 * s[math.ceil(0.9 * len(s)) - 1],
            "done_per_s": len(lat) / drain, "rho": busy / drain,
            "drain_s": drain - args.seconds,
            "served_from": {k: sum(r["served_from"] == k
                                   for r in run["flight"])
                            for k in ("memo", "journal", "search",
                                      "coalesced")},
            "late_max_s": run["late_max_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
