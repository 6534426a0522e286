"""Mean host time a tick of the gateway's per-frame bookkeeping (bucket
and session counters, the ring scatter's issue, lazy-sync accounting)
over the window, from the program's ``gateway.account`` phase
counters."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import _phases  # noqa: E402


def read(run):
    return _phases.window_mean_ms(run, "gateway.account")
