"""Reduce a ``jax.profiler`` trace to device busy time and idle share.

Busy time is the union of the intervals in which an operation ran on a
device; the idle share is one minus busy over the traced window. Device
planes are those named ``/device:<accelerator>:<n>``; on each, the line
of XLA operations is read (or, where a backend writes none, every line).
Busy time is averaged over the devices that have a plane.

Host spans that the benchmark writes as ``TraceAnnotation`` (one per
search, request or sweep) name the idle gaps: each gap is attributed to
the annotation that overlaps it most.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

OP_LINES = ("XLA Ops",)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], t0: int, t1: int) -> List[Interval]:
    """Idle intervals of ``[t0, t1)`` outside the merged ``busy`` cover."""
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


def _name_gap(gap: Interval, spans: Sequence[Tuple[str, int, int]]) -> str:
    best, name = 0, "no benchmark span"
    for n, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def reduce_planes(planes: Sequence[Dict], annotations: Sequence[str],
                  t0_ns: Optional[int] = None,
                  t1_ns: Optional[int] = None) -> Optional[Dict]:
    """Core reduction over plain planes: ``[{"name", "lines": [{"name",
    "events": [(name, start_ns, dur_ns)]}]}]``. The window defaults to
    the span of all host annotations. Returns None when no plane is a
    device's."""
    devices = [p for p in planes if p["name"].startswith("/device:")
               and not p["name"].startswith("/device:CPU")]
    spans = [(n, s, s + d) for p in planes if p["name"].startswith("/host:")
             for line in p["lines"] for n, s, d in line["events"]
             if n in annotations]
    if not devices:
        return None
    if t0_ns is None or t1_ns is None:
        if not spans:
            return None
        t0_ns = min(s for _, s, _ in spans)
        t1_ns = max(e for _, _, e in spans)
    busy_total, per_op, all_gaps = 0, {}, []
    for p in devices:
        lines = [l for l in p["lines"] if l["name"] in OP_LINES] \
            or p["lines"]
        ivs = []
        for line in lines:
            for n, s, d in line["events"]:
                s0, e0 = max(s, t0_ns), min(s + d, t1_ns)
                if e0 > s0:
                    ivs.append((s0, e0))
                    per_op[n] = per_op.get(n, 0) + (e0 - s0)
        cover = union(ivs)
        busy_total += sum(e - s for s, e in cover)
        all_gaps += gaps(cover, t0_ns, t1_ns)
    window = t1_ns - t0_ns
    busy = busy_total / len(devices)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy / 1e9,
        "window_s": window / 1e9,
        "idle_share": 1.0 - busy / window,
        "device_ops": [[n, v / 1e9] for n, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_name_gap(g, spans), (g[1] - g[0]) / 1e9]
                      for g in all_gaps[:10]],
    }


def load_planes(path: str) -> List[Dict]:
    """Plain planes of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [{"name": p.name,
             "lines": [{"name": l.name,
                        "events": [(e.name, int(e.start_ns),
                                    int(e.duration_ns)) for e in l.events]}
                       for l in p.lines]}
            for p in pd.planes]


def find_trace(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_trace(trace_dir: str, annotations: Sequence[str],
                 window: str) -> Optional[Dict]:
    """Reduce the newest trace under ``trace_dir``. ``window`` names the
    host annotation that spans the measured window."""
    planes = load_planes(find_trace(trace_dir))
    win = [(s, s + d) for p in planes if p["name"].startswith("/host:")
           for line in p["lines"] for n, s, d in line["events"]
           if n == window]
    t0, t1 = (win[0] if win else (None, None))
    return reduce_planes(planes, annotations, t0, t1)
