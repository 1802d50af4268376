"""The trace reduction: busy time as a union over the window, per-op
time, and idle gaps named by the host annotation around them — on a
made-up trace with known answers, and on a small trace recorded on a
TPU v5e, so every later change computes the same numbers."""

import pathlib
import types

import pytest

from bench import tracing

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _ev(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 end_ns=float(end),
                                 duration_ns=float(end - start))


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=n, events=evs)
                          for n, evs in lines.items()])


def _profile():
    host = _plane("/host:CPU", {"main": [
        _ev("window", 100, 1100), _ev("run_population", 100, 600),
        _ev("result_sync", 600, 700), _ev("submit", 900, 1000),
        _ev("before", 0, 90)]})
    dev0 = _plane("/device:TPU:0", {
        "XLA Ops": [_ev("fusion.1", 50, 200), _ev("sort_kernel", 150, 400),
                    _ev("fusion.1", 800, 850)],
        "XLA Modules": [_ev("jit_step", 50, 900)]})
    dev1 = _plane("/device:TPU:1", {"XLA Ops": [_ev("other", 0, 2000)]})
    return types.SimpleNamespace(planes=[host, dev0, dev1])


def test_busy_is_the_union_inside_the_window():
    red = tracing.Reduction(_profile(), devices=1)
    assert red.window_s == pytest.approx(1000e-9)
    # [100, 400) and [800, 850): overlapping ops count once
    assert red.busy_s == pytest.approx(350e-9)
    assert red.ops["fusion.1"] == [pytest.approx(150e-9), 2]
    assert red.ops["sort_kernel"] == [pytest.approx(250e-9), 1]


def test_devices_average_and_outside_devices_do_not_count():
    one = tracing.Reduction(_profile(), devices=1)
    two = tracing.Reduction(_profile(), devices=2)
    assert two.busy_s == pytest.approx((350e-9 + 1000e-9) / 2)
    assert "other" not in one.ops


def test_gaps_are_named_by_the_annotation_covering_most_of_them():
    red = tracing.Reduction(_profile(), devices=1)
    gaps = red.idle_gaps()
    assert sum(g[1] for g in gaps) == pytest.approx(650e-9)
    assert gaps[0] == ["run_population", pytest.approx(400e-9)]
    assert gaps[1] == ["submit", pytest.approx(250e-9)]
    assert red.top_ops(1) == [["sort_kernel", pytest.approx(250e-9)]]


def test_a_trace_without_the_window_is_refused():
    prof = _profile()
    prof.planes[0].lines[0].events = prof.planes[0].lines[0].events[1:]
    with pytest.raises(ValueError, match="window"):
        tracing.Reduction(prof, devices=1)


def _recorded():
    from jax.profiler import ProfileData
    return tracing.Reduction(ProfileData.from_file(
        str(DATA / "kmeans_small.xplane.pb")), devices=1)


def test_recorded_trace_reduces_to_the_same_numbers():
    # three generations of four 2**16-element Kmeans proxies on a v5e
    red = _recorded()
    assert red.window_s == pytest.approx(0.112328671, rel=1e-12)
    assert red.busy_s == pytest.approx(0.014485094, rel=1e-12)
    assert red.ops["%sort_rows.4"] == [pytest.approx(0.000194987,
                                                     rel=1e-9), 12]
    assert red.ops["%hash_mix.7"][1] == 12
    assert red.top_ops(1)[0][0] == "%fusion.54"
    assert all(not n.startswith("%while") for n in red.ops)
    gaps = red.idle_gaps(3)
    assert [g[0] for g in gaps] == ["run_population"] * 3
    assert gaps[0][1] == pytest.approx(0.014763418, rel=1e-9)
