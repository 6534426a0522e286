"""Mean host time a tick of the gateway's policy decision (observations,
``policy.decide``, bucketing) over the window, from the program's
``gateway.decide`` phase counters."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import _phases  # noqa: E402


def read(run):
    return _phases.window_mean_ms(run, "gateway.decide")
