"""Share of the window spent in the engine's dense per-candidate scoring
of layers whose edges mix ``FullMap`` with an exact map (a mixer reading
the MoE combine beside the shared expert, attention reading a fresh
cache append beside its queries): the ``engine.score_mixed_s`` counter
that ``OverlapEngine`` publishes, over the window's wall time. It is a
part of ``score_dense_share.search``. A program without that counter
reports nothing."""


def read(run):
    s = (run.get("counters") or {}).get("engine.score_mixed_s")
    if s is None:
        return None
    return 100.0 * s / run["window_s"]
