"""Diversify: mean diversification calls (``SearchStats.div_calls``: fused
rounds and div-A* runs) of the requests answered in the window."""
import numpy as np


def read(run):
    stats = run.answered_stats()
    if not stats:
        return None
    return float(np.mean([s.div_calls for s in stats]))
