"""Share of the population window in which the device ran nothing while
the host dispatched work to it: inside a ``stack.dispatch`` or
``hadoop.dispatch`` span of the program and outside the spill's copies
(``bench/spans.py``)."""

from bench import spans


def read(run):
    if run.kind != "population" or run.trace is None:
        return None
    return spans.idle_share(run.trace, ("stack.dispatch", "hadoop.dispatch"),
                            minus=("hadoop.h2d", "hadoop.d2h"))
