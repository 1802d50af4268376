"""Seconds the program spent compiling edge bodies for their cost
(``repro.core.engine.stats()["analyze_s"]``, read after the run; most of
it falls in set-up)."""


def read(run):
    from repro.core import engine
    return engine.stats().get("analyze_s")
