"""Scheduler: 95th percentile, in ms, of the wait from a request's due
instant to its admission to a lane (``Request.t_admit``)."""
import numpy as np


def read(run):
    waits = [s.req.t_admit - s.t_due for s in run.sent
             if s.req is not None and s.req.t_admit is not None]
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 95))
