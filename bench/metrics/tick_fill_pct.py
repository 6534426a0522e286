"""Frames per tick as a share of the tick width, over the window, from
the streaming runtime's counters."""


def read(run):
    a, b = run.stats["w0"], run.stats["w1"]
    ticks = b.ticks - a.ticks
    served = sum(b.frames_served.values()) - sum(a.frames_served.values())
    if ticks <= 0:
        return None
    return 100.0 * served / (ticks * run.max_batch)
