"""One minus the union of device-operation intervals over the traced
window (``bench/trace_reduce.py``)."""


def read(run):
    tr = run.get("trace")
    return None if tr is None else 100.0 * tr["idle_share"]
