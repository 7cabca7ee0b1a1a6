"""Share of the window spent committing chosen mappings: the summed
``search.commit`` spans (``layer_result`` per layer and the closing
``evaluate_chain`` of each search) over the window's wall time."""


def read(run):
    sp = (run.get("spans") or {}).get("search.commit")
    if not sp:
        return None
    return 100.0 * sum(sp) / run["window_s"]
