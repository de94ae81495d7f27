"""Service loop: 95th percentile of the wall time of one processed
rescan window, over every window of the run, those in which no decision
was made included."""
import numpy as np


def read(ctx):
    gaps = np.diff(ctx["stamps"])
    return float(1e3 * np.percentile(gaps, 95)) if gaps.size >= 200 else None
