"""95th percentile of the gaps between consecutive tokens of one request,
pooled over all requests due in the window."""
import numpy as np
from _lib import percentile

UNIT = "ms"


def read(run):
    gaps = [np.diff(q["stamps"]) * 1e3 for q in run["requests"]
            if len(q["stamps"]) > 1]
    return percentile(np.concatenate(gaps) if gaps else [], 95)
