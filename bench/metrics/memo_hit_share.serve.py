"""Requests of the window answered from the response memo, over those
that completed (flight recorder ``served_from``)."""


def read(run):
    fl = [r for r in run.get("flight", []) if r["outcome"] == "ok"]
    if not fl:
        return None
    return 100.0 * sum(r["served_from"] == "memo" for r in fl) / len(fl)
