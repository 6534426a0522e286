"""Open-loop latency of the frames due in the window: from when each was
due to when its result came back, in ms.  A frame refused at submit or
never served is given the time to the end of the run, so it counts as
late as anything measured."""
import numpy as np


def due_in_window(run, qos=None):
    a = run.arrivals
    if a is None:
        return None
    w0, w1 = run.window
    sel = (a["due"] >= w0) & (a["due"] < w1)
    if qos is not None:
        sel &= a["qos"] == qos
    return sel


def latency_ms(run, qos=None):
    sel = due_in_window(run, qos)
    if sel is None or not sel.any():
        return None
    a = run.arrivals
    end = np.nanmax(a["done"])
    done = np.where(np.isnan(a["done"]) | a["refused"], end, a["done"])
    return (done[sel] - a["due"][sel]) * 1e3


def p95(x):
    return None if x is None else float(np.percentile(x, 95))
