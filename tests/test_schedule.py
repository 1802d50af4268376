"""ExecutionPlan lowering contract: fused-stage execution must be
bit-identical to the unfused per-edge path (per stack and per dwarf
component), bucket schedules must be deterministic across processes, and
bucketed population execution must hold the ≤1-executable-per-bucket /
0-retrace contract."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # property test skips; deterministic tests run
    HAVE_HYPOTHESIS = False

from repro.api import ParamSpace, cache_stats, get_stack
from repro.core import schedule
from repro.core.dag import Edge, ProxyDAG
from repro.core.dwarfs import ComponentParams
from repro.core.dwarfs.base import REGISTRY
from repro.core.workloads import mega_chain

POP = 3
SIZE = 1024

#: per-component extras that must exist for the dynamic tunables to appear
_SEED_EXTRAS = {
    "hash": {"rounds": 2},
    "encryption": {"rounds": 2},
    "histogram": {"mix_rounds": 1},
    "grouped_count": {"mix_rounds": 1},
    "top_k": {"k": 8},
}

FUSE_ALL = 1e30


def _chain_dag(component: str, size: int = SIZE) -> ProxyDAG:
    """component -> hash chain on one buffer size: a fusable private
    linear chain exercising the component inside a fused switch loop."""
    return ProxyDAG(
        f"sched_{component}", {"src": size},
        [Edge(component, ["src"], "mid",
              ComponentParams(data_size=size, chunk_size=64, weight=2,
                              extra=dict(_SEED_EXTRAS.get(component, {})))),
         Edge("hash", ["mid"], "out",
              ComponentParams(data_size=size, chunk_size=128, weight=1,
                              extra={"rounds": 2}))],
        "out")


_CACHE = {}


def _component_fixture(component):
    """(dag, space, fused jitted pfn, unfused jitted pfn) built once per
    component — hypothesis examples step only *dynamic* params, so both
    executables compile exactly once."""
    if component not in _CACHE:
        dag = _chain_dag(component)
        space = ParamSpace.from_dag(dag)
        fused = schedule.lower(dag, threshold=FUSE_ALL, cache=False)
        unfused = schedule.lower(dag, threshold=0.0, cache=False)
        assert fused.fused_stage_count == 1, component
        assert unfused.fused_stage_count == 0
        _CACHE[component] = (dag, space,
                             jax.jit(fused.build_parametric()),
                             jax.jit(unfused.build_parametric()))
    return _CACHE[component]


def _assert_fused_matches_unfused(component, weights, extras):
    dag, space, fused, unfused = _component_fixture(component)
    base = space.values(dag)
    rows = np.tile(base, (POP, 1))
    for i, w in enumerate(weights):
        for li, leaf in enumerate(space.leaves):
            if leaf.dynamic:
                rows[i, li] = w if leaf.field == "weight" else extras[i]
    batched = space.stack_candidates(dag, rows)
    rng = jax.random.PRNGKey(0)
    for i, dyn in enumerate(space.unstack_candidates(batched)):
        a = np.asarray(fused(rng, dyn))
        b = np.asarray(unfused(rng, dyn))
        assert a == b, (
            f"{component}: candidate {i} (weight={weights[i]}, "
            f"extra={extras[i]}) fused {a!r} != unfused {b!r}")


# ---------------------------------------------------------------------------
# fused == unfused, bit-identical, per dwarf component (hypothesis sweep)
# ---------------------------------------------------------------------------


if HAVE_HYPOTHESIS:
    @pytest.mark.parametrize("component", sorted(REGISTRY))
    @given(data=st.data())
    def test_fused_stage_matches_unfused_per_component(component, data):
        weights = data.draw(st.lists(st.integers(0, 5), min_size=POP,
                                     max_size=POP), label="weights")
        extras = data.draw(st.lists(st.integers(1, 4), min_size=POP,
                                    max_size=POP), label="extras")
        _assert_fused_matches_unfused(component, weights, extras)


#: deterministic tier-1 subset of the hypothesis sweep above
_FAMILY_SUBSET = sorted({
    "matrix_multiplication", "monte_carlo", "hash", "encryption", "fft",
    "jaccard", "graph_traversal", "quick_sort", "top_k", "histogram",
    "grouped_count", "count_average",
})


@pytest.mark.parametrize("component", _FAMILY_SUBSET)
def test_fused_stage_matches_unfused_fixed(component):
    _assert_fused_matches_unfused(component, weights=[0, 2, 5],
                                  extras=[1, 3, 2])


# ---------------------------------------------------------------------------
# fused == unfused on every stack (threshold flip via the env knob)
# ---------------------------------------------------------------------------


def _stack_dag() -> ProxyDAG:
    return ProxyDAG(
        "sched_stacks", {"src": 2048},
        [Edge("quick_sort", ["src"], "a",
              ComponentParams(data_size=2048, chunk_size=128, weight=2)),
         Edge("hash", ["a"], "b",
              ComponentParams(data_size=2048, chunk_size=256, weight=3,
                              extra={"rounds": 2})),
         Edge("min_max", ["b"], "out",
              ComponentParams(data_size=2048, chunk_size=128, weight=1))],
        "out")


@pytest.mark.parametrize("stack_name", ["openmp", "mpi", "spark", "hadoop"])
def test_fused_run_matches_unfused_on_stack(stack_name, monkeypatch):
    stack = get_stack(stack_name)
    rng = jax.random.PRNGKey(0)
    monkeypatch.setenv("REPRO_FUSION_THRESHOLD", str(FUSE_ALL))
    assert schedule.lower(_stack_dag()).fused_stage_count == 1
    fused = stack.run(_stack_dag(), rng=rng)
    monkeypatch.setenv("REPRO_FUSION_THRESHOLD", "0")
    assert schedule.lower(_stack_dag()).fused_stage_count == 0
    unfused = stack.run(_stack_dag(), rng=rng)
    assert np.asarray(fused.result) == np.asarray(unfused.result)
    if stack_name == "hadoop":
        # spilling per fused stage (one chain spill) must move strictly
        # less host traffic than spilling per edge
        assert 0.0 < fused.io_bytes < unfused.io_bytes


def test_fused_population_matches_unfused_on_stack(monkeypatch):
    dag = _stack_dag()
    space = ParamSpace.from_dag(dag)
    matrix = space.sample_dynamic(5, space.values(dag), seed=7)
    stack = get_stack("openmp")
    monkeypatch.setenv("REPRO_FUSION_THRESHOLD", str(FUSE_ALL))
    fused = np.asarray(stack.run_population(dag, matrix).result)
    monkeypatch.setenv("REPRO_FUSION_THRESHOLD", "0")
    unfused = np.asarray(stack.run_population(dag, matrix).result)
    np.testing.assert_array_equal(fused, unfused)


# ---------------------------------------------------------------------------
# lowering + plan cache
# ---------------------------------------------------------------------------


def test_lower_caches_per_structure_and_threshold():
    d1, d2 = _stack_dag(), _stack_dag()
    d2.edges[0].params.weight = 9          # dynamic step: same structure
    p1 = schedule.lower(d1, threshold=FUSE_ALL)
    p2 = schedule.lower(d2, threshold=FUSE_ALL)
    assert p1 is p2                        # one plan per (structure, thr)
    p3 = schedule.lower(d1, threshold=0.0)
    assert p3 is not p1
    assert p3.structure_key() != p1.structure_key()   # partition in the key
    d3 = _stack_dag()
    d3.edges[0].params.data_size = 4096    # structural step: new plan
    assert schedule.lower(d3, threshold=FUSE_ALL) is not p1


def test_fusion_requires_private_linear_chain():
    # "a" feeds two consumers -> edge 0 must not fuse into edge 1
    dag = ProxyDAG(
        "diamond", {"src": 1024},
        [Edge("hash", ["src"], "a",
              ComponentParams(data_size=1024, chunk_size=64, weight=1,
                              extra={"rounds": 1})),
         Edge("min_max", ["a"], "b",
              ComponentParams(data_size=1024, chunk_size=64, weight=1)),
         Edge("histogram", ["a", "b"], "out",
              ComponentParams(data_size=1024, chunk_size=64, weight=1))],
        "out")
    plan = schedule.lower(dag, threshold=FUSE_ALL, cache=False)
    assert plan.partition() == ((0,), (1,), (2,))


def test_threshold_zero_is_one_stage_per_edge():
    plan = schedule.lower(_stack_dag(), threshold=0.0, cache=False)
    assert plan.partition() == ((0,), (1,), (2,))
    assert plan.fused_stage_count == 0


def test_fused_plan_has_fewer_loop_ops():
    dag = ProxyDAG(
        "two_mm", {"src": 1024},
        [Edge("matrix_multiplication", ["src"], "a",
              ComponentParams(data_size=1024, chunk_size=64, weight=2)),
         Edge("matrix_multiplication", ["a"], "out",
              ComponentParams(data_size=1024, chunk_size=64, weight=3))],
        "out")
    rng = jax.random.PRNGKey(0)
    dyn = dag.dynamic_params()

    def loops(jaxpr):
        n = 0
        for eq in jaxpr.eqns:
            if eq.primitive.name in ("while", "scan"):
                n += 1
            for v in eq.params.values():
                for vv in (v if isinstance(v, (list, tuple)) else (v,)):
                    if hasattr(vv, "jaxpr"):
                        n += loops(vv.jaxpr)
        return n

    unfused = schedule.lower(dag, threshold=0.0, cache=False)
    fused = schedule.lower(dag, threshold=FUSE_ALL, cache=False)
    ju = jax.make_jaxpr(unfused.build_parametric())(rng, dyn)
    jf = jax.make_jaxpr(fused.build_parametric())(rng, dyn)
    assert loops(jf.jaxpr) < loops(ju.jaxpr)


# ---------------------------------------------------------------------------
# bucket schedules
# ---------------------------------------------------------------------------


def _schedule_fingerprint(bucket_size=None) -> str:
    dag = _stack_dag()
    space = ParamSpace.from_dag(dag)
    matrix = space.sample_dynamic(16, space.values(dag), seed=13)
    plan = schedule.lower(dag, threshold=0.0, cache=False)
    sched = plan.bucket_schedule(space.stack_candidates(dag, matrix),
                                 bucket_size)
    return json.dumps({
        "signature": list(sched.signature),
        "buckets": [[b.indices.tolist(), b.valid, b.trip_bound]
                    for b in sched.buckets],
    })


def test_bucket_schedule_is_deterministic_across_processes():
    want = _schedule_fingerprint(bucket_size=4)
    code = (
        "import sys, tests.test_schedule as t;"
        "sys.stdout.write(t._schedule_fingerprint(bucket_size=4))")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "src"),
         env.get("PYTHONPATH", "")])
    env.setdefault("JAX_PLATFORMS", "cpu")
    got = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, check=True).stdout
    assert got == want


def test_bucket_schedule_invariants():
    dag = _stack_dag()
    space = ParamSpace.from_dag(dag)
    matrix = space.sample_dynamic(10, space.values(dag), seed=3)
    plan = schedule.lower(dag, threshold=0.0, cache=False)
    sched = plan.bucket_schedule(space.stack_candidates(dag, matrix), 4)
    assert sched.signature == (3, 4)
    # every bucket padded to one shared size; real indices partition [0, n)
    seen = []
    for b in sched.buckets:
        assert b.indices.shape == (4,)
        seen.extend(b.indices[:b.valid].tolist())
    assert sorted(seen) == list(range(10))
    # stratified: bucket cost bounds are nondecreasing
    bounds = [b.cost_bound for b in sched.buckets]
    assert bounds == sorted(bounds)
    masses = sched.bucket_masses()
    assert masses.shape == (3,) and masses.sum() == pytest.approx(1.0)


def test_bucketed_execution_matches_single_batch():
    dag = _stack_dag()
    space = ParamSpace.from_dag(dag)
    matrix = space.sample_dynamic(8, space.values(dag), seed=21)
    stack = get_stack("openmp")
    whole = np.asarray(
        stack.run_population(dag, matrix, bucket_size=8).result)
    for bs in (1, 3, 4):
        got = np.asarray(
            stack.run_population(dag, matrix, bucket_size=bs).result)
        np.testing.assert_array_equal(got, whole)


# ---------------------------------------------------------------------------
# ≤1 executable per bucket signature / 0 retraces across sweeps
# ---------------------------------------------------------------------------


def test_bucketed_sweep_compiles_one_executable_and_never_retraces():
    from repro.api.stack import OpenMPStack
    dag = _stack_dag()
    space = ParamSpace.from_dag(dag)
    base = space.values(dag)
    stack = OpenMPStack()                 # fresh executable cache
    m0 = cache_stats()["misses"]
    stack.run_population(dag, space.sample_dynamic(12, base, seed=0),
                         bucket_size=3)
    assert cache_stats()["misses"] - m0 == 1   # one executable, 4 buckets
    t0, m1 = cache_stats()["traces"], cache_stats()["misses"]
    for seed in (1, 2, 3):
        rep = stack.run_population(dag,
                                   space.sample_dynamic(12, base, seed=seed),
                                   bucket_size=3)
        assert rep.batch == 12
    # population-size changes re-bucket onto the same executable
    stack.run_population(dag, space.sample_dynamic(9, base, seed=4),
                         bucket_size=3)
    assert cache_stats()["traces"] == t0
    assert cache_stats()["misses"] == m1


def test_default_bucket_size_follows_devices_and_env(monkeypatch):
    monkeypatch.delenv("REPRO_POP_BUCKETS", raising=False)
    assert schedule.resolve_bucket_size(16) == max(1, min(
        16, jax.device_count()))
    monkeypatch.setenv("REPRO_POP_BUCKETS", "4")
    assert schedule.resolve_bucket_size(16) == 4
    assert schedule.resolve_bucket_size(3) == 1
    monkeypatch.setenv("REPRO_POP_BUCKETS", "1")
    assert schedule.resolve_bucket_size(16) == 16


# ---------------------------------------------------------------------------
# megakernel: one-kernel fused stages ≡ the fori_loop + switch path
# ---------------------------------------------------------------------------


def _mega_dag() -> ProxyDAG:
    """A private linear chain whose members all have registered megakernel
    segment bodies (quick_sort/hash/top_k/min_max) — fused under FUSE_ALL
    it lowers to a single mega-eligible stage."""
    return mega_chain()


def _run_plan(plan, dag):
    """Fresh-jitted scalar result (a new jit per call, so flipping env
    knobs between calls always retraces)."""
    out = jax.jit(plan.build_parametric())(jax.random.PRNGKey(0),
                                           dag.dynamic_params())
    return np.asarray(out)


@pytest.fixture
def pallas_env(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("REPRO_MEGAKERNEL", raising=False)


def test_megakernel_stage_is_bit_identical_to_fori_path(pallas_env,
                                                        monkeypatch):
    dag = _mega_dag()
    fused = schedule.lower(dag, threshold=FUSE_ALL, cache=False)
    unfused = schedule.lower(dag, threshold=0.0, cache=False)
    assert fused.partition() == ((0, 1, 2, 3),)
    assert fused.stages[0].mega                 # structural eligibility
    assert fused.mega_stage_count == 1
    assert unfused.mega_stage_count == 0

    schedule.reset_mega_stats()
    a = _run_plan(fused, dag)                   # megakernel engaged
    assert schedule.mega_stats() == {"mega": 1, "fallback": 0}

    monkeypatch.setenv("REPRO_MEGAKERNEL", "0")
    schedule.reset_mega_stats()
    b = _run_plan(fused, dag)                   # same plan, fori+switch
    assert schedule.mega_stats() == {"mega": 0, "fallback": 1}
    monkeypatch.delenv("REPRO_MEGAKERNEL", raising=False)

    c = _run_plan(unfused, dag)                 # per-edge path
    assert a == b, f"megakernel {a!r} != fori_loop {b!r}"
    assert a == c, f"megakernel {a!r} != unfused {c!r}"


def test_megakernel_matches_across_weight_steps(pallas_env, monkeypatch):
    """Dynamic weights are the one traced input the kernel accepts (the
    per-segment trip bound): stepping them must track the fori path
    bit-for-bit, including zero-trip members."""
    dag = _mega_dag()
    fused = schedule.lower(dag, threshold=FUSE_ALL, cache=False)
    space = ParamSpace.from_dag(dag)
    rows = space.sample_dynamic(4, space.values(dag), seed=11)
    # force one candidate to all-zero weights (identity stage)
    for li, leaf in enumerate(space.leaves):
        if leaf.dynamic and leaf.field == "weight":
            rows[0, li] = 0
    batched = space.stack_candidates(dag, rows)
    rng = jax.random.PRNGKey(0)
    jm = jax.jit(fused.build_parametric())
    monkeypatch.setenv("REPRO_MEGAKERNEL", "0")
    jf = jax.jit(fused.build_parametric())
    monkeypatch.delenv("REPRO_MEGAKERNEL", raising=False)
    for dyn in space.unstack_candidates(batched):
        got = np.asarray(jm(rng, dyn))
        with_fori = None
        try:
            monkeypatch.setenv("REPRO_MEGAKERNEL", "0")
            with_fori = np.asarray(jf(rng, dyn))
        finally:
            monkeypatch.delenv("REPRO_MEGAKERNEL", raising=False)
        assert got == with_fori


def test_megakernel_degrades_under_forced_xla(pallas_env):
    """The circuit breaker's forced-XLA override must demote a mega stage
    to the stock path *and* produce the stock result — the degrade
    contract extends through the megakernel."""
    from repro.kernels.dispatch import forced_backend
    dag = _mega_dag()
    fused = schedule.lower(dag, threshold=FUSE_ALL, cache=False)
    assert fused.stages[0].mega
    schedule.reset_mega_stats()
    with forced_backend("xla"):
        degraded = _run_plan(fused, dag)
    st = schedule.mega_stats()
    assert st["mega"] == 0 and st["fallback"] == 1
    with forced_backend("xla"):
        stock = _run_plan(schedule.lower(dag, threshold=0.0, cache=False),
                          dag)
    assert degraded == stock


def test_megakernel_flag_is_part_of_exec_cache_key(pallas_env, monkeypatch):
    """Flipping REPRO_MEGAKERNEL between runs on one stack must compile a
    second executable, never reuse one traced for the other lowering."""
    from repro.api.stack import OpenMPStack
    dag = _mega_dag()
    monkeypatch.setenv("REPRO_FUSION_THRESHOLD", str(FUSE_ALL))
    stack = OpenMPStack()
    a = np.asarray(stack.run(dag, rng=jax.random.PRNGKey(0)).result)
    m0 = stack.exec_domain().stats["misses"]
    monkeypatch.setenv("REPRO_MEGAKERNEL", "0")
    b = np.asarray(stack.run(dag, rng=jax.random.PRNGKey(0)).result)
    assert stack.exec_domain().stats["misses"] == m0 + 1
    assert a == b
