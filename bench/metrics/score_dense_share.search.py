"""Share of the window spent in the engine's dense per-candidate
scoring (its ready-step pass and ``_score_forward_one``): the
``engine.score_dense_s`` counter that ``OverlapEngine`` publishes, over
the window's wall time."""


def read(run):
    s = (run.get("counters") or {}).get("engine.score_dense_s")
    if s is None:
        return None
    return 100.0 * s / run["window_s"]
