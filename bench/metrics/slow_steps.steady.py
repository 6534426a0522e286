"""Serving iterations of 50 ms or more in the window, each counted once
whichever phase inside it was slow, from the program's
``stream_phase_slow_total{phase="step"}`` counter."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import _phases  # noqa: E402


def read(run):
    d = _phases.delta(run, "stream.step")
    return None if d is None else float(d[2])
