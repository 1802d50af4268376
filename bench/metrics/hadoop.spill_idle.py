"""Share of the population window in which the device ran nothing while
the host copied spilled buffers: inside a ``hadoop.h2d`` or
``hadoop.d2h`` span of the program (``bench/spans.py``)."""

from bench import spans


def read(run):
    if run.kind != "population" or run.trace is None:
        return None
    return spans.idle_share(run.trace, ("hadoop.h2d", "hadoop.d2h"))
