"""Population evaluation: vmapped candidate batches must be bit-identical
to sequential per-candidate execution, compile like a single candidate,
and round-trip through the ParamSpace stack/unstack helpers."""

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # property test skips; deterministic tests run
    HAVE_HYPOTHESIS = False

from repro.api import ParamSpace, cache_stats, get_stack
from repro.core import PopulationTuner, engine
from repro.core.dag import Edge, ProxyDAG, _checksum
from repro.core.dwarfs import ComponentParams
from repro.core.dwarfs.base import REGISTRY
from repro.core.proxy import ProxyBenchmark

POP = 3          # fixed per-example population (one compile per component)
SIZE = 1024

#: per-component extras that must exist for the dynamic tunables to appear
#: as ParamSpace leaves (apply() defaults don't create leaves)
_SEED_EXTRAS = {
    "hash": {"rounds": 2},
    "encryption": {"rounds": 2},
    "histogram": {"mix_rounds": 1},
    "grouped_count": {"mix_rounds": 1},
    "top_k": {"k": 8},
}

_CACHE = {}


def _component_fixture(component):
    """(dag, space, base vector), built once per component so hypothesis
    examples share one compiled structure."""
    if component not in _CACHE:
        dag = ProxyDAG(
            f"pop_{component}", {"src": SIZE},
            [Edge(component, ["src"], "out",
                  ComponentParams(data_size=SIZE, chunk_size=64, weight=1,
                                  extra=dict(_SEED_EXTRAS.get(component,
                                                              {}))))],
            "out")
        space = ParamSpace.from_dag(dag)
        _CACHE[component] = (dag, space, space.values(dag))
    return _CACHE[component]


def _candidate_matrix(space, base, weights, extras):
    rows = np.tile(base, (len(weights), 1))
    for i, w in enumerate(weights):
        for leaf_i, leaf in enumerate(space.leaves):
            if not leaf.dynamic:
                continue
            rows[i, leaf_i] = w if leaf.field == "weight" else extras[i]
    return rows


# ---------------------------------------------------------------------------
# property: vmapped == sequential, bit-identical, for every dwarf component
# ---------------------------------------------------------------------------


def _assert_population_matches_sequential(component, weights, extras):
    dag, space, base = _component_fixture(component)
    matrix = _candidate_matrix(space, base, weights, extras)
    stack = get_stack("openmp")
    pop = np.asarray(
        stack.run_population(dag, matrix, space=space).result)
    for i in range(POP):
        trial = ProxyBenchmark(dag).clone()
        space.apply(trial.dag, matrix[i])
        single = np.asarray(stack.run(trial, rng=jax.random.PRNGKey(0)).result)
        assert pop[i] == single, (
            f"{component}: candidate {i} (weight={weights[i]}, "
            f"extra={extras[i]}) vmapped {pop[i]!r} != sequential {single!r}")


if HAVE_HYPOTHESIS:
    @pytest.mark.parametrize("component", sorted(REGISTRY))
    @given(data=st.data())
    def test_vmapped_population_matches_sequential(component, data):
        weights = data.draw(st.lists(st.integers(0, 5), min_size=POP,
                                     max_size=POP), label="weights")
        extras = data.draw(st.lists(st.integers(1, 4), min_size=POP,
                                    max_size=POP), label="extras")
        _assert_population_matches_sequential(component, weights, extras)


#: one representative per dwarf family plus every dynamic-extra component —
#: the deterministic tier-1 subset of the hypothesis sweep above
_FAMILY_SUBSET = sorted({
    "matrix_multiplication", "monte_carlo", "hash", "encryption", "fft",
    "jaccard", "graph_traversal", "quick_sort", "top_k", "histogram",
    "grouped_count", "count_average",
})


@pytest.mark.parametrize("component", _FAMILY_SUBSET)
def test_vmapped_population_matches_sequential_fixed(component):
    _assert_population_matches_sequential(component, weights=[0, 2, 5],
                                          extras=[1, 3, 2])


@pytest.mark.parametrize("stack_name", ["mpi", "spark", "hadoop"])
def test_population_matches_sequential_on_distributed_stacks(stack_name):
    dag = ProxyDAG(
        "pop_stacks", {"src": 2048},
        [Edge("quick_sort", ["src"], "mid",
              ComponentParams(data_size=2048, chunk_size=128, weight=2)),
         Edge("hash", ["mid"], "out",
              ComponentParams(data_size=2048, chunk_size=256, weight=1,
                              extra={"rounds": 2}))],
        "out")
    space = ParamSpace.from_dag(dag)
    matrix = space.sample_dynamic(4, space.values(dag), seed=11)
    stack = get_stack(stack_name)
    rep = stack.run_population(dag, matrix, rng=jax.random.PRNGKey(0))
    assert rep.batch == 4
    pop = np.asarray(rep.result)
    for i in range(4):
        trial = ProxyBenchmark(dag).clone()
        space.apply(trial.dag, matrix[i])
        single = np.asarray(stack.run(trial, rng=jax.random.PRNGKey(0)).result)
        assert pop[i] == single
    if stack_name == "hadoop":
        assert rep.io_bytes > 0


# ---------------------------------------------------------------------------
# compile-once contract: a 16-candidate population costs one executable
# ---------------------------------------------------------------------------


def _sweep_dag():
    return ProxyDAG(
        "pop_sweep", {"src": 2048},
        [Edge("matrix_multiplication", ["src"], "mm",
              ComponentParams(data_size=2048, chunk_size=64, weight=2)),
         Edge("top_k", ["mm"], "out",
              ComponentParams(data_size=2048, chunk_size=128, weight=1,
                              extra={"k": 8}))],
        "out")


def test_16_candidate_population_compiles_at_most_one_executable():
    stack = get_stack("openmp")
    dag = _sweep_dag()
    space = ParamSpace.from_dag(dag)
    base = space.values(dag)

    m0 = cache_stats()["misses"]
    stack.run(dag, rng=jax.random.PRNGKey(0))
    single_compiles = cache_stats()["misses"] - m0

    m1 = cache_stats()["misses"]
    stack.run_population(dag, space.sample_dynamic(16, base, seed=0))
    pop_compiles = cache_stats()["misses"] - m1
    assert pop_compiles <= max(single_compiles, 1)

    # the population sweep itself: new candidate batches, zero retraces
    t0, m2 = cache_stats()["traces"], cache_stats()["misses"]
    for seed in (1, 2, 3):
        rep = stack.run_population(dag, space.sample_dynamic(16, base,
                                                             seed=seed))
        assert rep.batch == 16
        assert np.asarray(rep.result).shape == (16,)
    assert cache_stats()["traces"] == t0
    assert cache_stats()["misses"] == m2


def test_population_size_change_reuses_the_bucket_executable():
    # executables are keyed on (plan, bucket size), not population size:
    # growing or shrinking the population re-buckets onto the same
    # compiled program — only an explicit bucket-size change compiles
    stack = get_stack("openmp")
    dag = _sweep_dag()
    space = ParamSpace.from_dag(dag)
    base = space.values(dag)
    stack.run_population(dag, space.sample_dynamic(8, base, seed=0))
    t0 = cache_stats()["traces"]
    stack.run_population(dag, space.sample_dynamic(8, base, seed=1))
    assert cache_stats()["traces"] == t0          # same schedule: cache hit
    stack.run_population(dag, space.sample_dynamic(4, base, seed=1))
    assert cache_stats()["traces"] == t0          # same bucket size: hit
    stack.run_population(dag, space.sample_dynamic(8, base, seed=2),
                         bucket_size=8)
    assert cache_stats()["traces"] == t0 + 1      # new bucket size: compile


# ---------------------------------------------------------------------------
# stack/unstack helpers
# ---------------------------------------------------------------------------


def test_build_population_equals_parametric_per_candidate(rng):
    dag = _sweep_dag()
    space = ParamSpace.from_dag(dag)
    matrix = space.sample_dynamic(4, space.values(dag), seed=2)
    batched = space.stack_candidates(dag, matrix)
    pop = np.asarray(jax.jit(dag.build_population())(rng, batched))
    pfn = jax.jit(dag.build_parametric())
    for i, dyn in enumerate(space.unstack_candidates(batched)):
        assert pop[i] == np.asarray(pfn(rng, dyn))


def test_stack_candidates_roundtrips_through_unstack():
    dag = _sweep_dag()
    space = ParamSpace.from_dag(dag)
    matrix = space.sample_dynamic(5, space.values(dag), seed=3)
    batched = space.stack_candidates(dag, matrix)
    singles = space.unstack_candidates(batched)
    assert len(singles) == 5
    for i, dyn in enumerate(singles):
        trial = ProxyBenchmark(dag).clone()
        space.apply(trial.dag, matrix[i])
        expect = trial.dag.dynamic_params()
        assert jax.tree.structure(dyn) == jax.tree.structure(expect)
        for got, want in zip(jax.tree.leaves(dyn), jax.tree.leaves(expect)):
            assert got.dtype == want.dtype
            assert np.asarray(got) == np.asarray(want)


def test_stack_candidates_rejects_static_drift():
    dag = _sweep_dag()
    space = ParamSpace.from_dag(dag)
    matrix = space.sample_dynamic(3, space.values(dag), seed=0)
    matrix[1, space.index_of("e0.matrix_multiplication.data_size")] *= 2
    with pytest.raises(ValueError, match="static"):
        space.stack_candidates(dag, matrix)
    with pytest.raises(ValueError, match="static"):
        engine.measure_population(dag, space, matrix)


# ---------------------------------------------------------------------------
# population scorer: vectorized metrics == sequential engine.measure
# ---------------------------------------------------------------------------


def test_measure_population_matches_sequential_measure():
    dag = _sweep_dag()
    space = ParamSpace.from_dag(dag)
    matrix = space.sample_dynamic(8, space.values(dag), seed=5)
    engine.measure(dag)                         # warm the per-edge caches
    t0 = engine.stats()["traces"]
    pop = engine.measure_population(dag, space, matrix)
    assert engine.stats()["traces"] == t0       # scoring never executes
    for i in range(8):
        trial = ProxyBenchmark(dag).clone()
        space.apply(trial.dag, matrix[i])
        seq = engine.measure(trial.dag)
        for k, v in seq.items():
            assert pop[i][k] == pytest.approx(v, rel=1e-9, abs=1e-12), (
                f"candidate {i} metric {k}")


def test_tuner_slot_allocation_is_exact_even_for_zero_mass():
    tuner = PopulationTuner({"mix_sort": 1.0}, population=12)
    # proportional split sums exactly to the slot count
    counts = tuner._slot_allocation(np.array([0.5, 0.3, 0.2]), 10)
    assert counts.sum() == 10 and (counts > 0).all()
    # zero-mass population (every weight evolved to 0): round-robin, no
    # broadcast crash in _evolve
    counts = tuner._slot_allocation(np.zeros(3), 10)
    assert counts.sum() == 10


def test_tuner_evolves_zero_weight_population_without_crashing():
    dag = _sweep_dag()
    target = engine.measure(dag)
    tuner = PopulationTuner(target, population=6, generations=2, seed=0,
                            execute=False)
    from repro.api import ParamSpace
    space = ParamSpace.from_dag(dag)
    tuner._space, tuner._dyn_mask = space, space.dynamic_mask()
    tuner._base = space.values(dag)
    tuner._scorer = engine.PopulationScorer(dag, space)
    matrix = np.tile(tuner._base, (6, 1))
    matrix[:, tuner._dyn_mask] = 0.0            # all weights pruned
    out = tuner._evolve(matrix, np.zeros(6), gen=1)
    assert out.shape == matrix.shape


def test_tuner_search_buckets_hold_multiple_candidates():
    # search stratification must not collapse to the per-device execution
    # bucket size (1 on CPU): singleton "elites" would make the evolution
    # accuracy-blind
    tuner = PopulationTuner({"mix_sort": 1.0}, population=16)
    assert tuner._search_bucket_size(16) >= 2


def test_population_tuner_runs_generations_deterministically():
    dag = _sweep_dag()
    target = engine.measure(dag)
    start = ProxyBenchmark(_sweep_dag())
    start.dag.edges[0].params.weight = 8        # detune a dynamic leaf
    kw = dict(tol=1e-9, population=6, generations=3, seed=42, execute=False)
    res1 = PopulationTuner(target, **kw).tune(start)
    res2 = PopulationTuner(target, **kw).tune(start)
    assert res1.generations == res2.generations
    assert res1.candidates_evaluated == res2.candidates_evaluated <= 18
    assert res1.final_accuracy["avg"] == res2.final_accuracy["avg"]
    assert res1.final_accuracy["avg"] >= res1.initial_accuracy["avg"]


@pytest.mark.parametrize("n", [0, 1, 5, 1000, 1 << 12])
def test_output_checksum_is_the_sum_in_a_width_independent_order(n):
    """The DAG result's reduction: a lane reduced alone and inside a vmap
    gives the same bits, and the value is the sum of the elements."""
    x = jax.random.normal(jax.random.PRNGKey(n), (4, n))
    one = jax.jit(_checksum)
    many = jax.jit(jax.vmap(_checksum))(x)
    for i in range(4):
        assert np.asarray(one(x[i])).tobytes() == \
            np.asarray(many[i]).tobytes()
        np.testing.assert_allclose(float(one(x[i])),
                                   np.sum(np.asarray(x[i], np.float64)),
                                   rtol=1e-5, atol=1e-4)
    ints = jax.numpy.arange(n, dtype=jax.numpy.int32)
    assert int(_checksum(ints)) == n * (n - 1) // 2
