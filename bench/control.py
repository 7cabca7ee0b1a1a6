"""Readings that set a cell's limits: the program's, and the control's.

    python3 bench/control.py --workload resnet18.search-c48 \
        --seeds 11,12,13 --control-seeds 21,22,23

For each ``--seeds`` seed it runs the program's timed path once at the
cell's size and prints the numbers the cell compares (the lower
readings). For each ``--control-seeds`` seed it puts the plain reference,
computed in float32, in the program's place and prints the same numbers
(the upper readings). The program computes in float64; the control is
the nearest lower precision. The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness, program, reference, traffic  # noqa: E402


def _search_answer(drv, seed: int, control: bool):
    if not control:
        return drv.dep.search(drv._cfg(seed))
    net = reference.Network(drv.net["layers"], drv.net["edges"])
    ref = reference.search(net, drv.dep.cfg["arch"], drv._params(seed),
                           dtype=np.float32)
    return {"seed": seed, "total": ref["total"], "ends": ref["ends"],
            "energy": ref["energy"], "chosen": ref["chosen"]}


def _points(drv, kind: str, seed: int):
    """(search seed, objective, record) of the points a run judges."""
    if kind == "dse_loop":
        sw = program.dse_sweep(drv.dep, drv.p, seed)
        return [(seed, drv.dep.cfg["objective"], r) for r in sw["records"]]
    out = []
    for o in drv.p["objectives"]:
        code, body = traffic.http_post(drv.server.url, drv._body(o, seed))
        if code != 200:
            raise RuntimeError(f"request failed: {code} {body}")
        out.append((seed, o, body["best"]))
    return out


def readings(drv, kind: str, seed: int, control: bool):
    """The cell's compared numbers for one seed."""
    if kind == "search_loop":
        run = traffic.Run(searches=[_search_answer(drv, seed, control)])
        return drv.judge(run)
    p = drv.p if kind == "dse_loop" else drv.p["request"]
    gap = 0.0
    pts = _points(drv, kind, seed)[:drv.p["judged"]]
    for s, objective, rec in pts:
        cfg = dict(drv.dep.cfg, objective=objective)
        if control:
            net = reference.Network(drv.net["layers"], drv.net["edges"])
            ref = reference.search(
                net, reference.arch_for_point(cfg["arch"], rec["point"]),
                {"seed": s, "n_candidates": p["n_candidates"],
                 "max_steps": p["max_steps"], "objective": objective},
                dtype=np.float32)
            rec = dict(rec, total_ns=ref["total"], energy_pj=ref["energy"])
        gap = max(gap, traffic._point_gap(drv.net, cfg, p, s, rec,
                                          np.float64))
    return [("answer_gap", gap, drv.p["limits"]["answer_gap"])]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    manifest = harness.load_manifest()
    w, c = harness.cell(manifest, args.workload)
    print(harness.check_device(w["chips"]), file=sys.stderr)
    with open(os.path.join(harness.ROOT, c["file"])) as fh:
        dep = program.Deployment(json.load(fh))
    mix = traffic.load(w["traffic"])
    drv = traffic.make(mix, dep, 0)
    drv.setup()
    try:
        for flag, seeds in ((False, args.seeds), (True, args.control_seeds)):
            for s in (int(x) for x in seeds.split(",") if x):
                t = time.perf_counter()
                rows = readings(drv, mix["kind"], s, flag)
                print(json.dumps({
                    "workload": args.workload, "seed": s,
                    "side": "control" if flag else "program",
                    "seconds": time.perf_counter() - t,
                    **{n: v for n, v, _ in rows}}), flush=True)
    finally:
        release = getattr(drv, "release", None)
        if release is not None:
            release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
