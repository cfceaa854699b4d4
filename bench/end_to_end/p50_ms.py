"""Median latency, in ms, of every request due in the window, from the
instant it was due to its answer (open-loop cells)."""
import numpy as np


def read(run):
    if run.loop != "open" or not run.sent:
        return None
    return 1e3 * float(np.percentile(run.latencies(), 50))
