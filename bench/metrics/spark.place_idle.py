"""Share of the population window in which the device ran nothing while
the spark stack placed a bucket's parameters and key on its mesh: inside
a ``spark.place`` span of the program (``bench/spans.py``).  It lies
inside ``stack.dispatch``, so it is a part of ``pop.dispatch_idle``."""

from bench import spans


def read(run):
    if run.kind != "population" or run.trace is None:
        return None
    return spans.idle_share(run.trace, ("spark.place",))
