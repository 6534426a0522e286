"""Mean host time a tick of issuing the tick's device work (per-bucket
gathers and split-engine chains, the concatenation and the reassembly
gather) over the window, from the program's ``gateway.dispatch``
phase counters."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import _phases  # noqa: E402


def read(run):
    return _phases.window_mean_ms(run, "gateway.dispatch")
