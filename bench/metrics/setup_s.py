"""Process start to window start: imports, device check, lowering,
warm-up and any compilation."""


def read(run):
    return run["setup_s"]
