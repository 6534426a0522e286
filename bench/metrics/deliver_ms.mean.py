"""Mean host time a collected tick of result delivery (the runtime's books
and the ``on_result`` callbacks) over the window, from the program's
``stream.deliver`` phase counters."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import _phases  # noqa: E402


def read(run):
    return _phases.window_mean_ms(run, "stream.deliver")
