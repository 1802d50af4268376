"""Share of the population window in which the device ran nothing: one
minus the union of its op intervals over the window, averaged over the
cell's chips (``bench/tracing.py``)."""


def read(run):
    if run.kind != "population" or run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
