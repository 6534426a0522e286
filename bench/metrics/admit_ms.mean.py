"""Mean host time a serving iteration of the streaming runtime's admission
(scheduler admit and shed under the queue lock, lock wait included, and
the hand-off to the gateway) over the window, from the program's
``stream.admit`` phase counters."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import _phases  # noqa: E402


def read(run):
    return _phases.window_mean_ms(run, "stream.admit")
