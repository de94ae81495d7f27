"""Allocation layer: wall time per successful head-of-queue placement in
the traced window (the engine's audit stream ``on_alloc``: MILP build and
HiGHS solve, or the heuristic)."""


def read(ctx):
    c = ctx["layer_clock"]
    if c is None or c.placements == 0:
        return None
    return 1e3 * c.alloc_s / c.placements
