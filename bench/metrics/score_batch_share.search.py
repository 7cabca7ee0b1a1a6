"""Share of the window spent in the engine's batched class-histogram
scorer: the ``engine.score_batch_s`` counter that ``OverlapEngine``
publishes, over the window's wall time."""


def read(run):
    s = (run.get("counters") or {}).get("engine.score_batch_s")
    if s is None:
        return None
    return 100.0 * s / run["window_s"]
