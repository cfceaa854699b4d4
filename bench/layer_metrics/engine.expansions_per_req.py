"""Engine: mean beam-search expansions (``SearchStats.expansions``) of the
requests answered in the window."""
import numpy as np


def read(run):
    stats = run.answered_stats()
    if not stats:
        return None
    return float(np.mean([s.expansions for s in stats]))
