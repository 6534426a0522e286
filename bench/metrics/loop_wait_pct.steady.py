"""Share of the window the serving thread spent waiting for work on the
queue condition, from the program's ``stream.wait`` phase counters."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import _phases  # noqa: E402


def read(run):
    d = _phases.delta(run, "stream.wait")
    w0, w1 = run.window
    if d is None or w1 <= w0:
        return None
    return 100.0 * d[1] / (w1 - w0)
