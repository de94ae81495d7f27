"""Device layer: share of the traced window in which no operation ran on
the chip, 100 * (1 - busy / window), from the profiler trace."""


def read(ctx):
    red = ctx["trace_result"]
    if red is None or red["window_s"] <= 0 or red["devices"] == 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
