"""Window wall time over the searches (or DSE points evaluated) that
completed in it; the window ends with the last search started inside it."""


def read(run):
    if not run.get("completed"):
        return None
    return run["window_s"] / run["completed"]
