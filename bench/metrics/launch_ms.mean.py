"""Mean host time of the gateway's tick launch (decide, stage, dispatch
and host bookkeeping) over the window, from the benchmark's span around
``tick_launch``."""


def read(run):
    s = run.spans.in_window("bench.tick_launch", *run.window)
    if not s:
        return None
    return 1e3 * sum(b - a for a, b in s) / len(s)
