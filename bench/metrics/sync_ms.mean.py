"""Mean host time a tick spent waiting for the tick's embeddings and
copying them to the host over the window, from the program's
``gateway.sync`` phase counters."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import _phases  # noqa: E402


def read(run):
    return _phases.window_mean_ms(run, "gateway.sync")
