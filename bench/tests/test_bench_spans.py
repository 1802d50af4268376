"""Device idle time under the program's spans (``bench/spans.py``) and
the readers built on it and on the program's counters: exact shares on
a made-up trace, nothing (and no error) from a program without the
spans or counters, and every new metric in a traced run of each
population cell."""

import types

import pytest

from bench import spans, tracing
from benchkit import REPO, run_small
from bench.harness import metric_reader


def _ev(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 end_ns=float(end),
                                 duration_ns=float(end - start))


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=n, events=evs)
                          for n, evs in lines.items()])


def _reduction(program=True):
    """Window [0, 1000); device 0 busy [100, 300) and [500, 600), so idle
    [0, 100), [300, 500) and [600, 1000): 70% of the window."""
    main = [_ev("window", 0, 1000), _ev("run_population", 0, 1000)]
    worker = []
    if program:
        main += [_ev("hadoop.stage", 50, 700), _ev("hadoop.h2d", 50, 150),
                 _ev("hadoop.dispatch", 150, 250),
                 _ev("hadoop.wait", 250, 450), _ev("hadoop.d2h", 450, 700)]
        worker = [_ev("hadoop.dispatch", 650, 680),
                  _ev("stack.dispatch", 800, 900)]
    host = _plane("/host:CPU", {"main": main, "worker": worker})
    dev = _plane("/device:TPU:0", {"XLA Ops": [_ev("fusion.1", 100, 300),
                                               _ev("fusion.2", 500, 600)]})
    return tracing.Reduction(types.SimpleNamespace(planes=[host, dev]),
                             devices=1)


def test_interval_algebra():
    a = [(0, 10), (20, 30)]
    b = [(5, 25)]
    assert spans.intersect(a, b) == [(5, 10), (20, 25)]
    assert spans.subtract(a, b) == [(0, 5), (25, 30)]
    assert spans.subtract(b, a) == [(10, 20)]
    assert spans.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert spans.length(a) == 20


def test_idle_shares_under_spans_are_exact():
    red = _reduction()
    assert spans.length(spans.idle(red)) == 700
    # idle under h2d or d2h: [50, 100), [450, 500), [600, 700)
    assert spans.idle_share(red, ("hadoop.h2d", "hadoop.d2h")) \
        == pytest.approx(20.0)
    # idle under a dispatch: [650, 680) lies under d2h and goes; only
    # [800, 900) stays
    assert spans.idle_share(red, ("stack.dispatch", "hadoop.dispatch"),
                            minus=("hadoop.h2d", "hadoop.d2h")) \
        == pytest.approx(10.0)


def test_idle_partitions_by_innermost_span_and_sums_to_idle():
    part = spans.idle_by_span(_reduction())
    assert part == {"none": pytest.approx(25.0),
                    "hadoop.wait": pytest.approx(15.0),
                    "hadoop.d2h": pytest.approx(12.0),
                    "stack.dispatch": pytest.approx(10.0),
                    "hadoop.h2d": pytest.approx(5.0),
                    "hadoop.dispatch": pytest.approx(3.0)}
    assert sum(part.values()) == pytest.approx(70.0)


def _run(red, **kw):
    fields = dict(kind="population", trace=red, stack="hadoop", reports=[],
                  evals=0)
    fields.update(kw)
    return types.SimpleNamespace(**fields)


def test_readers_read_the_spans_and_the_counters():
    red = _reduction()
    assert metric_reader(REPO, "hadoop.spill_idle")(_run(red)) \
        == pytest.approx(20.0)
    assert metric_reader(REPO, "pop.dispatch_idle")(_run(red)) \
        == pytest.approx(10.0)
    reports = [types.SimpleNamespace(h2d_bytes=96.0, d2h_bytes=40.0),
               types.SimpleNamespace(h2d_bytes=32.0, d2h_bytes=24.0)]
    run = _run(red, reports=reports, evals=8)
    assert metric_reader(REPO, "hadoop.h2d_bytes_per_eval")(run) == 16.0
    assert metric_reader(REPO, "hadoop.d2h_bytes_per_eval")(run) == 8.0
    assert metric_reader(REPO, "hadoop.h2d_bytes_per_eval")(
        _run(red, reports=reports, evals=8, stack="openmp")) is None


def test_readers_give_nothing_for_a_program_without_them(monkeypatch):
    red = _reduction(program=False)
    for name in ("hadoop.spill_idle", "pop.dispatch_idle"):
        assert metric_reader(REPO, name)(_run(red)) is None
    reports = [types.SimpleNamespace(io_bytes=1.0)]
    for name in ("hadoop.h2d_bytes_per_eval", "hadoop.d2h_bytes_per_eval"):
        assert metric_reader(REPO, name)(_run(red, reports=reports,
                                              evals=16)) is None
    from repro.core import engine
    monkeypatch.setattr(engine, "stats", lambda: {"compiles": 3})
    assert metric_reader(REPO, "setup.cost_analysis_s")(_run(red)) is None


NEW = {"terasort.pop": {"hadoop.spill_idle", "pop.dispatch_idle",
                        "hadoop.h2d_bytes_per_eval",
                        "hadoop.d2h_bytes_per_eval",
                        "setup.cost_analysis_s"},
       "kmeans.pop": {"pop.dispatch_idle", "setup.cost_analysis_s"}}


@pytest.mark.parametrize("workload", sorted(NEW))
def test_a_traced_run_prints_the_new_metrics(small_root, workload):
    result, _ = run_small(small_root, workload, trace=1)
    assert result["correct"] is True
    got = result["metrics"]
    assert NEW[workload] <= set(got)
    assert all(got[m]["value"] >= 0 for m in NEW[workload])
    if workload == "terasort.pop":
        assert got["hadoop.h2d_bytes_per_eval"]["value"] \
            > got["hadoop.d2h_bytes_per_eval"]["value"] > 0
    assert got["setup.cost_analysis_s"]["value"] > 0


def test_the_longest_gaps_are_partitioned_in_milliseconds():
    gaps = spans.longest_gaps(_reduction(), k=2)
    # [600, 1000) and [300, 500), in ns; the test's clock unit is 1 ns
    assert [g["ms"] for g in gaps] == [pytest.approx(400e-6),
                                       pytest.approx(200e-6)]
    assert gaps[0]["spans"] == {"none": pytest.approx(200e-6),
                                "stack.dispatch": pytest.approx(100e-6),
                                "hadoop.d2h": pytest.approx(70e-6),
                                "hadoop.dispatch": pytest.approx(30e-6)}
    assert gaps[1]["spans"] == {"hadoop.wait": pytest.approx(150e-6),
                                "hadoop.d2h": pytest.approx(50e-6)}
