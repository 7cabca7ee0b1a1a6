"""Share of the window spent scoring all-``FullMap`` layers in closed
form (one ready constant and one grouped transform call per pool): the
``engine.score_full_s`` counter that ``OverlapEngine`` publishes, over
the window's wall time. A program without that counter reports
nothing."""


def read(run):
    s = (run.get("counters") or {}).get("engine.score_full_s")
    if s is None:
        return None
    return 100.0 * s / run["window_s"]
