"""Bytes the hadoop stack copied from host to device per candidate, as
the program counts them where it copies (``RunReport.h2d_bytes``)."""


def read(run):
    got = [getattr(r, "h2d_bytes", None) for r in run.reports]
    if run.stack != "hadoop" or run.evals == 0 or None in got:
        return None
    return sum(got) / run.evals
