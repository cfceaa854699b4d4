"""Scheduler: mean share of lanes holding a request, in %, sampled at each
backend step of the window (the benchmark's span around ``step``)."""
import numpy as np


def read(run):
    if not run.occupancy:
        return None
    return 100.0 * float(np.mean(run.occupancy))
