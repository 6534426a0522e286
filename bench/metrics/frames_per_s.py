"""Frames served per second: results delivered in the window over its
length (host clock)."""
import numpy as np


def read(run):
    w0, w1 = run.window
    t = run.result_times
    return float(np.count_nonzero((t >= w0) & (t < w1)) / (w1 - w0))
