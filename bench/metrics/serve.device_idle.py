"""Share of the serving window in which the device ran nothing (the same
reduction as ``pop.device_idle``)."""


def read(run):
    if run.kind != "poisson" or run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
