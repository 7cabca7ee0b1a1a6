"""Share of the DSE sweeps' wall time spent outside point evaluation:
1 - sum(dse.evaluate_batch) / sum(dse.sweep), from the obs spans."""


def read(run):
    sp = run.get("spans") or {}
    sweep = sum(sp.get("dse.sweep", []))
    if not sweep:
        return None
    return 100.0 * (1.0 - sum(sp.get("dse.evaluate_batch", [])) / sweep)
