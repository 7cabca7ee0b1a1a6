"""Share of the window spent computing ready matrices through generic
coordinate maps (head folds and weight maps, the ``if todo`` pass of
``ready_steps_batch``): the ``engine.ready_cmap_s`` counter that
``OverlapEngine`` publishes, over the window's wall time. A program
without that counter reports nothing."""


def read(run):
    s = (run.get("counters") or {}).get("engine.ready_cmap_s")
    if s is None:
        return None
    return 100.0 * s / run["window_s"]
