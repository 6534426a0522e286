"""Mean host time of the gateway's ``open_session`` (admission and the
ring read-back) over the sessions opened in set-up, from the program's
``gateway.open_session`` phase counters at the window's start."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import _phases  # noqa: E402


def read(run):
    p = _phases.phases(run.stats["w0"])
    return None if p is None else _phases.mean_ms(
        p.get("gateway.open_session"))
