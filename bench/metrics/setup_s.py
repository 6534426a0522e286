"""Set-up: from the process's start to the window's, with the weights,
mel pool, fleet and sessions built and every program warmed."""


def read(run):
    return run.setup_s
