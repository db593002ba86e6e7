"""Share of the window in which no operation ran on the device (the
union of the trace's op intervals, averaged over the chips used)."""


def read(run):
    if run.trace is None or not run.trace.planes:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
