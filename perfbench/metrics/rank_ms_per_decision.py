"""Ranking layer: wall time the engine spent in the prioritizer per
decision in the traced window (the engine's audit stream, ``rank_wall_s``:
features, the actor's device call, the deep scorer)."""


def read(ctx):
    c = ctx["layer_clock"]
    if c is None or c.decisions == 0:
        return None
    return 1e3 * c.rank_s / c.decisions
