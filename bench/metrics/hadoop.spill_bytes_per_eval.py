"""Bytes the hadoop stack moved through host memory per candidate: the
window's ``RunReport.io_bytes`` over the candidates counted."""


def read(run):
    if run.stack != "hadoop" or run.evals == 0:
        return None
    return sum(r.io_bytes for r in run.reports) / run.evals
