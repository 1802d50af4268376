"""Serving-engine contract: deterministic traces and reports, the
zero-retrace steady state after warmup, micro-batched bit-identity
against sequential ``Stack.run``, and pool eviction under pressure."""

import copy

import numpy as np
import pytest

from repro.api.stack import OpenMPStack, cache_stats
from repro.core.pool import get_pool, pool_stats
from repro.serve.engine import (ArrivalTrace, ServingEngine, burst_trace,
                                poisson_trace, serve)

MIX = ("terasort", "kmeans")


@pytest.fixture(scope="module")
def trace():
    return poisson_trace(n=8, rate_rps=200.0, seed=11, mix=MIX)


@pytest.fixture(scope="module")
def engine(trace):
    eng = ServingEngine(stack="openmp", max_batch=4, bucket_size=2)
    eng.warmup(trace)
    return eng


def test_trace_is_deterministic_and_mixed(trace):
    again = poisson_trace(n=8, rate_rps=200.0, seed=11, mix=MIX)
    assert [r.arrival_s for r in again] == [r.arrival_s for r in trace]
    assert [r.structure for r in again] == [r.structure for r in trace]
    assert trace.structures == sorted(set(MIX))
    assert len(trace.unique_dags()) == 2
    arr = [r.arrival_s for r in trace]
    assert arr == sorted(arr) and arr[0] > 0.0
    # per-request dynamic params actually vary within a structure
    by_s = {}
    for r in trace:
        by_s.setdefault(r.structure, []).append(r)
    for rs in by_s.values():
        if len(rs) > 1:
            a = np.concatenate([np.ravel(v) for d in rs[0].dyn
                                for v in d.values()])
            b = np.concatenate([np.ravel(v) for d in rs[1].dyn
                                for v in d.values()])
            assert not np.array_equal(a, b)


def test_burst_trace_capacity_mode():
    tr = burst_trace(n=6, bursts=1, seed=0, mix=MIX)
    assert all(r.arrival_s == 0.0 for r in tr)
    tr4 = burst_trace(n=8, bursts=4, period_s=0.01, seed=0, mix=MIX)
    assert sorted(set(r.arrival_s for r in tr4)) == [0.0, 0.01, 0.02, 0.03]


def test_virtual_clock_reports_are_identical_across_runs(engine, trace):
    a = engine.serve(trace, clock="virtual", mode="open")
    b = engine.serve(trace, clock="virtual", mode="open")
    assert a.latency_s == b.latency_s
    assert a.queue_wait_s == b.queue_wait_s
    assert a.service_s == b.service_s
    assert a.throughput_rps == b.throughput_rps
    assert a.makespan_s == b.makespan_s
    assert a.batch_hist == b.batch_hist
    assert a.retraces == 0 and a.cold_dispatches == 0
    assert a.n_requests == len(trace) and a.structures == 2
    # percentile ordering sanity
    for d in (a.latency_s, a.queue_wait_s, a.service_s):
        assert d["p50"] <= d["p95"] <= d["p99"] <= d["max"]


def test_zero_retraces_after_warmup(engine, trace):
    first = engine.serve(trace, clock="wall", mode="open")
    second = engine.serve(trace, clock="wall", mode="open")
    for rep in (first, second):
        assert rep.retraces == 0
        assert rep.cold_dispatches == 0
        assert rep.compile_s == 0.0
        assert rep.n_requests == len(trace)
        assert rep.throughput_rps > 0
        assert rep.time_to_first_result_s > 0
        assert sum(k * v for k, v in rep.batch_hist.items()) >= len(trace)


def test_microbatched_results_match_sequential_stack_run(engine, trace):
    rep = engine.serve(trace, clock="wall", mode="open")
    assert all(r is not None for r in rep.results)
    stack = OpenMPStack()
    for req, got in zip(trace, rep.results):
        clone = copy.deepcopy(req.dag)
        for edge, dyn in zip(clone.edges, req.dyn):
            for field, v in dyn.items():
                if field == "weight":
                    edge.params.weight = float(v)
                else:
                    edge.params.extra[field] = float(v)
        want = stack.run(clone, rng=req.rng).result
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_closed_loop_serves_one_request_per_dispatch(engine, trace):
    rep = engine.serve(trace, clock="virtual", mode="closed")
    assert rep.mode == "closed"
    assert rep.batch_hist == {1: len(trace)}
    assert rep.queue_wait_s["max"] == 0.0


def test_convenience_serve_and_report_json():
    tr = poisson_trace(n=4, rate_rps=500.0, seed=2, mix=("terasort",))
    rep = serve(tr, stack="openmp", clock="wall", mode="open",
                max_batch=2, bucket_size=2)
    assert rep.retraces == 0           # serve() warms up by default
    d = rep.to_json()
    assert "results" not in d
    assert set(d["latency_s"]) == {"p50", "p95", "p99", "mean", "max"}
    assert d["resources"]["host_rss_peak_bytes"] > 0


def test_eviction_under_cache_pressure(monkeypatch, trace):
    monkeypatch.setenv("REPRO_EXEC_CACHE_CAP", "1")
    stack = OpenMPStack()              # fresh instance: its own pool domain
    eng = ServingEngine(stack=stack, max_batch=4, bucket_size=2)
    rep = eng.serve(trace, clock="wall", mode="open")
    dom = stack.exec_domain()
    assert rep.n_requests == len(trace)
    # two alternating structures under a one-executable cap must churn
    assert len(dom.cache) <= 1
    assert dom.stats["evictions"] > 0
    assert rep.cold_dispatches > 0


def test_pool_cost_eviction_prefers_cheapest(monkeypatch):
    """Under pool-cap pressure the default policy evicts the executable
    that is cheapest to recompile (plan cost model), not the oldest;
    ``REPRO_POOL_POLICY=fifo`` restores the legacy order.  Artifacts
    admitted without a cost count as 0.0 — the preferred victims."""
    from repro.core.pool import ExecutablePool
    monkeypatch.delenv("REPRO_POOL_POLICY", raising=False)
    pool = ExecutablePool(cap=2)
    dom = pool.register("t:cost")
    pool.put(dom, "expensive", object(), cost=100.0)
    pool.put(dom, "cheap", object(), cost=1.0)
    pool.put(dom, "mid", object(), cost=10.0)   # over cap -> evict cheapest
    assert set(dom.cache) == {"expensive", "mid"}
    st = pool.stats()
    assert st["pool_policy"] == "cost"
    assert st["evictions_by_policy"]["pool_cost"] == 1
    pool.put(dom, "uncosted", object())          # no cost -> 0.0 -> victim
    pool.put(dom, "pricey", object(), cost=50.0)
    assert set(dom.cache) == {"expensive", "pricey"}
    monkeypatch.setenv("REPRO_POOL_POLICY", "fifo")
    pool.put(dom, "late", object(), cost=0.5)    # fifo -> evict oldest
    assert set(dom.cache) == {"pricey", "late"}
    assert pool.stats()["evictions_by_policy"]["pool_fifo"] == 1


def test_stats_surfaces_expose_hit_rate(engine, trace):
    engine.serve(trace, clock="wall", mode="open")
    cs = cache_stats()
    assert 0.0 <= cs["hit_rate"] <= 1.0
    ps = pool_stats()
    assert ps is get_pool().stats() or ps == get_pool().stats()
    doms = ps["domains"]
    assert any(name.startswith("stack:openmp") for name in doms)
    assert "plans" in doms and "engine:body" in doms
    for d in doms.values():
        assert d["size"] >= 0 and 0.0 <= d["hit_rate"] <= 1.0
    assert ps["executables"] == sum(d["size"] for d in doms.values()
                                    if d["kind"] == "executable")
    assert ps["hits"] == sum(d["hits"] for d in doms.values())
