"""Bytes the hadoop stack copied from device to host per candidate, as
the program counts them where it copies (``RunReport.d2h_bytes``)."""


def read(run):
    got = [getattr(r, "d2h_bytes", None) for r in run.reports]
    if run.stack != "hadoop" or run.evals == 0 or None in got:
        return None
    return sum(got) / run.evals
