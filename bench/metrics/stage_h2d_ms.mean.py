"""Mean host time a tick of staging the tick's mels (``np.stack``, pow2
padding, ``device_put``) over the window, from the program's
``gateway.stage_h2d`` phase counters.  The host side only: the copy
itself shows on the device trace."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import _phases  # noqa: E402


def read(run):
    return _phases.window_mean_ms(run, "gateway.stage_h2d")
