"""p95 of how late the load generator sent the frames due in the window
(its submit call against the due time), in ms."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
import _latency  # noqa: E402


def read(run):
    sel = _latency.due_in_window(run)
    if sel is None or not sel.any():
        return None
    return float(np.percentile(run.arrivals["late"][sel], 95) * 1e3)
