"""Verify: share, in %, of the window's answers that carry a Theorem-2
certificate (``SearchStats.certified``)."""
import numpy as np


def read(run):
    stats = run.answered_stats()
    if not stats:
        return None
    return 100.0 * float(np.mean([bool(s.certified) for s in stats]))
