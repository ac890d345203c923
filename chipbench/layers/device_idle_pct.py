"""Device: the share of the window in which no operation ran on the
chip, from the profiler's trace."""


def read(run):
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
