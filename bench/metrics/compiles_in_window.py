"""XLA programs built (compiled, or loaded from the persistent cache)
inside the window; set-up warms them all, so this reads 0."""


def read(run):
    return float(run.compiles.count(*run.window))
