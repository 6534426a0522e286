"""p95 of all frames due in the window, due time to result."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import _latency  # noqa: E402


def read(run):
    return _latency.p95(_latency.latency_ms(run))
