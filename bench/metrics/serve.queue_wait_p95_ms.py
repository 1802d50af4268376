"""95th percentile of the live engine's queue wait (dispatcher pick-up
minus submit, ``ServeReport.queue_wait_s`` from ``shutdown()``)."""


def read(run):
    if run.serve_report is None or run.serve_report.n_requests == 0:
        return None
    return 1e3 * run.serve_report.queue_wait_s["p95"]
