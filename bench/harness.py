"""Run one benchmark cell once and print its result line.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
deployment file of its configuration (``bench/configs``), the traffic
file (``bench/traffic/<traffic>.json``, read by ``traffic.py``) and one
reader per metric (``bench/metrics/<metric>.py``, a ``read(run)``
function that returns a number or None).

Order of a run: check the device, build and warm (``setup_s`` ends
here), measure the window (traced in its own run with ``--trace 1``),
read the device's peak memory, then judge the window's answers against
the plain reference, and print. Standard error ends with each number
compared beside its limit; standard output ends with the JSON line.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoDevice(RuntimeError):
    """No accelerator of a kind in the peaks table, or too few of them."""


def load_manifest(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cell(manifest: Dict, workload: str):
    """(workload entry, config entry) of one cell by name."""
    wl = {w["name"]: w for w in manifest["workloads"]}
    if workload not in wl:
        raise KeyError(f"no workload {workload!r}; have {sorted(wl)}")
    w = wl[workload]
    cfg = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    return w, cfg


def metrics_for(manifest: Dict, workload: str, trace: bool) -> List[Dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    metrics; a metric without ``workloads`` belongs to every cell."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


def reader(name: str, root: str = HERE):
    path = os.path.join(root, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_device(chips: int, root: str = HERE) -> Dict:
    """The device as JAX reports it; raises ``NoDevice`` unless there are
    ``chips`` accelerators of a kind listed in ``peaks.json``."""
    import jax
    devs = jax.devices()
    with open(os.path.join(root, "peaks.json")) as fh:
        peaks = json.load(fh)["devices"]
    d0 = devs[0]
    if d0.platform == "cpu" or d0.device_kind not in peaks:
        raise NoDevice(f"device {d0.platform}/{d0.device_kind} is not in "
                       f"bench/peaks.json ({sorted(peaks)})")
    if len(devs) < chips:
        raise NoDevice(f"{len(devs)} devices, the cell needs {chips}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips}


def peak_bytes(chips: int) -> int:
    import jax
    out = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: str = ROOT,
             device: Optional[Dict] = None) -> Dict:
    """One run of one cell; returns the result line as a dict.

    ``device`` given skips the look for a chip (tests only)."""
    bench = os.path.join(root, "bench")
    manifest = load_manifest(root)
    w, c = cell(manifest, workload)
    if device is None:
        device = check_device(w["chips"], bench)
    print(f"device: platform={device['platform']} "
          f"device_kind={device['kind']} count={device['count']}",
          file=sys.stderr, flush=True)
    from . import program, traffic as gen
    with open(os.path.join(root, c["file"])) as fh:
        dep_cfg = json.load(fh)
    dep = program.Deployment(dep_cfg)
    mix = gen.load(w["traffic"], bench)
    driver = gen.make(mix, dep, seed)
    driver.setup()
    driver.warm()
    # set-up's objects leave the collector's young generations, so the
    # window pays only for what it allocates itself
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        if trace:
            import jax
            from repro import obs
            tel = obs.enable(trace_path=os.path.join(tdir, "spans.jsonl"))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # annotations only
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with gen.annotate("window"):
                run = driver.window(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
                counters = tel.registry.snapshot()["counters"]
                obs.disable()
        if trace:
            from . import trace_reduce
            run["counters"] = counters
            run["spans"] = _span_totals(os.path.join(tdir, "spans.jsonl"))
            run["trace"] = trace_reduce.reduce_trace(
                tdir, gen.ANNOTATIONS, "window")
    gc.unfreeze()
    device = dict(device, memory_peak_bytes=peak_bytes(w["chips"]))
    if trace and run.get("trace"):
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
    run["setup_s"] = setup_s
    release = getattr(driver, "release", None)
    if release is not None:
        release()

    checks = driver.judge(run)
    correct = all(v <= lim for _, v, lim in checks)
    metrics = {}
    for m in metrics_for(manifest, workload, trace):
        v = reader(m["name"], bench)(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": correct, "attempted": run["attempted"],
           "failed": run["failed"], "metrics": metrics, "device": device}
    if trace and run.get("trace"):
        out["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                            "idle_gaps": run["trace"]["idle_gaps"]}
    for line in driver_lines(run):
        print(line, file=sys.stderr)
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in checks:
        print(f"check {n}: {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    return out


def driver_lines(run: Dict) -> List[str]:
    """Plain lines about the window (how late the generator ran, etc.)."""
    return [f"window: {k}={run[k]!r}" for k in
            ("window_s", "completed", "attempted", "failed", "late_p50_s",
             "late_max_s") if k in run]


def _span_totals(path: str) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    if not os.path.exists(path):
        return out
    with open(path) as fh:
        for line in fh:
            ev = json.loads(line)
            if ev.get("ev") == "span":
                out.setdefault(ev["name"], []).append(ev["dur_s"])
    return out


def main(argv: List[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(HERE, ".jax_cache"))
    # cache every program, however quick its compile
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start)
    except NoDevice as e:
        print(f"no device: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0
