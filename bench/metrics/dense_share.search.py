"""Share of candidate scores that took the engine's dense per-candidate
fallback over all scores of the window: the ``engine.dense_scored`` and
``engine.batch_scored`` counters that ``OverlapEngine`` publishes."""


def read(run):
    c = run.get("counters") or {}
    dense = c.get("engine.dense_scored", 0)
    n = dense + c.get("engine.batch_scored", 0)
    if not n:
        return None
    return 100.0 * dense / n
