"""Service loop: median wall time of one processed rescan window (feed
its arrivals, step the engine, return), over every window of the run,
those in which no decision was made included."""
import numpy as np


def read(ctx):
    gaps = np.diff(ctx["stamps"])
    return float(1e3 * np.percentile(gaps, 50)) if gaps.size else None
