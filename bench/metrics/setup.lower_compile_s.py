"""Seconds of the harness's span around lowering the cell's proxy and
warming its executables (part of ``setup_s``)."""


def read(run):
    return run.spans.get("lower_compile_s")
