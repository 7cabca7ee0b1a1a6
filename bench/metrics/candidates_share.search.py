"""Share of the window spent drawing candidate pools: the summed
``search.candidates`` spans (one per layer of each search) over the
window's wall time."""


def read(run):
    sp = (run.get("spans") or {}).get("search.candidates")
    if not sp:
        return None
    return 100.0 * sum(sp) / run["window_s"]
