"""Unit tests: the eight dwarf components (semantics + robustness)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dwarfs import DWARFS, REGISTRY, ComponentParams, get_component
from repro.core.dwarfs.base import as_u32, fit_buffer, mix_u32
from repro.core.dwarfs import statistic
from repro.core.dwarfs.graph import _edges_from_buffer
from repro.core.dwarfs.statistic import (DENSE_GROUPS_MAX, grouped_counts,
                                         grouped_stats, reset_grouped_stats,
                                         reset_segment_max_stats, segment_max,
                                         segment_max_stats)

P = ComponentParams(data_size=2048, chunk_size=128, parallelism=1, weight=1)


def test_registry_covers_all_eight_dwarfs():
    assert {c.dwarf for c in REGISTRY.values()} == set(DWARFS)
    assert len(REGISTRY) >= 24  # >= 3 components per dwarf


@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_component_runs_finite(name, rng):
    comp = REGISTRY[name]
    x = jax.random.normal(rng, (P.data_size,))
    out = jax.jit(lambda x, r: comp(x, P, r))(x, rng)
    assert np.isfinite(np.asarray(out)).all()
    assert out.ndim == 1 and out.shape[0] > 0


def test_quick_sort_sorts_rows(rng):
    comp = get_component("quick_sort")
    x = jax.random.normal(rng, (2048,))
    out = np.asarray(comp(x, P, rng)).reshape(-1, P.chunk_size)
    assert (np.diff(out, axis=1) >= 0).all()


def test_top_k_values_descend(rng):
    comp = get_component("top_k")
    x = jax.random.normal(rng, (2048,))
    out = np.asarray(comp(x, P.replace(extra={"k": 16}), rng))
    rows = out.reshape(-1, P.chunk_size)[:, :16]
    assert (np.diff(rows, axis=1) <= 1e-6).all()


def test_histogram_counts_consistent(rng):
    comp = get_component("histogram")
    x = jax.random.normal(rng, (2048,))
    p = P.replace(extra={"bins": 16})
    out = np.asarray(comp(x, p, rng))
    # output = counts[bin(x)] / N: every value in (0, 1], sums finite
    assert (out > 0).all() and (out <= 1.0).all()


def test_hash_deterministic_and_avalanche(rng):
    comp = get_component("hash")
    x = jax.random.normal(rng, (2048,))
    a = np.asarray(comp(x, P, rng))
    b = np.asarray(comp(x, P, rng))
    assert (a == b).all()
    # flipping one input element changes a bounded, nonzero set of outputs
    x2 = x.at[7].set(x[7] + 1.0)
    c = np.asarray(comp(x2, P, rng))
    assert (a != c).any()


def test_set_intersection_against_numpy(rng):
    comp = get_component("set_intersection")
    x = jax.random.normal(rng, (2048,))
    p = P.replace(extra={"buckets": 64})
    out = np.asarray(comp(x, p, rng))
    keys = np.asarray(as_u32(fit_buffer(x, 2048))) % 64
    h = 1024
    a, b = keys[:h], keys[h:]
    expected_nonzero = len(np.intersect1d(a, b)) > 0
    assert (np.count_nonzero(out[:h]) > 0) == expected_nonzero


def test_graph_construction_degree_mass(rng):
    comp = get_component("graph_construction")
    x = jax.random.normal(rng, (2048,))
    p = P.replace(extra={"vertices": 64})
    out = np.asarray(comp(x, p, rng))
    # gathered out_deg[src] + in_deg[dst]: strictly positive, mean >= 2
    assert (out >= 1.0).all()
    assert out.mean() >= 2.0


def test_spmv_conserves_rank_mass(rng):
    comp = get_component("spmv")
    x = jax.random.normal(rng, (4096,))
    p = P.replace(extra={"vertices": 128})
    out = np.asarray(comp(x, p, rng))
    assert np.isfinite(out).all() and (out >= 0).all()


def test_parallelism_lanes_change_shape_not_values_distribution(rng):
    comp = get_component("count_average")
    x = jax.random.normal(rng, (4096,))
    a = np.asarray(comp(x, P.replace(data_size=4096), rng))
    b = np.asarray(comp(x, P.replace(data_size=4096, parallelism=4), rng))
    assert a.shape == b.shape
    assert abs(a.std() - b.std()) < 0.5


def test_weight_zero_means_pruned():
    p = ComponentParams(weight=0).rounded()
    assert p.weight == 0


# ---------------------------------------------------------------------------
# grouped counts: dense path against the scatter
# ---------------------------------------------------------------------------

GROUPED_N = 4096


def _ids(v, groups, rounds):
    u = mix_u32(as_u32(v), rounds)
    return (u % jnp.uint32(groups)).astype(jnp.int32)


@pytest.mark.parametrize("rounds", ["static", "traced"])
@pytest.mark.parametrize("groups", [1, 16, 256, DENSE_GROUPS_MAX + 1])
def test_grouped_counts_match_segment_sum(groups, rounds, rng):
    """Counts equal the scatter's, and a lane alone gives the bits it
    gives inside a vmap of 4."""
    xs = jax.random.normal(rng, (4, GROUPED_N))
    static = (1,) if rounds == "static" else ()
    r = 2 if rounds == "static" else jnp.int32(2)

    def body(v, r):
        return grouped_counts(_ids(v, groups, r), groups)

    lanes = jax.jit(jax.vmap(body, in_axes=(0, None)),
                    static_argnums=static)(xs, r)
    run = jax.jit(body, static_argnums=static)
    for i in range(xs.shape[0]):
        counts = np.asarray(run(xs[i], r))
        np.testing.assert_array_equal(counts, np.asarray(lanes[i]))
        ref = jax.ops.segment_sum(jnp.ones((GROUPED_N,)),
                                  _ids(xs[i], groups, 2), num_segments=groups)
        np.testing.assert_array_equal(counts, np.asarray(ref))


@pytest.mark.parametrize("groups", [16, 256, DENSE_GROUPS_MAX + 1])
def test_grouped_count_lane_alone_equals_vmap_lane(groups, rng):
    comp = get_component("grouped_count")
    p = P.replace(data_size=GROUPED_N, extra={"groups": groups})
    xs = jax.random.normal(rng, (4, GROUPED_N))
    lanes = np.asarray(jax.jit(jax.vmap(
        lambda v: comp.apply(v, p, rng)))(xs))
    for i in range(xs.shape[0]):
        alone = np.asarray(jax.jit(lambda v: comp.apply(v, p, rng))(xs[i]))
        np.testing.assert_array_equal(alone, lanes[i])


def _body(name, groups):
    comp = get_component(name)
    key = "groups" if name == "grouped_count" else "bins"

    def body(v):
        p = ComponentParams(data_size=v.shape[0], chunk_size=64,
                            extra={key: groups, "mix_rounds": 1})
        return comp.apply(v, p, jax.random.PRNGKey(0))
    return body


@pytest.mark.parametrize("name,groups,path,scatters", [
    ("grouped_count", 16, "dense", 1),          # the sums' scatter alone
    ("grouped_count", DENSE_GROUPS_MAX, "dense", 1),
    ("grouped_count", DENSE_GROUPS_MAX + 1, "scatter", 2),
    ("histogram", 16, "dense", 0),
    ("histogram", DENSE_GROUPS_MAX + 1, "scatter", 1)])
def test_grouped_lowering_path(name, groups, path, scatters):
    reset_grouped_stats()
    text = jax.jit(_body(name, groups)).lower(
        jax.ShapeDtypeStruct((GROUPED_N,), jnp.float32)).compile().as_text()
    assert text.count(" scatter(") == scatters
    assert grouped_stats() == {"dense": int(path == "dense"),
                               "scatter": int(path == "scatter")}


@pytest.mark.parametrize("groups", [16, 256])
def test_grouped_count_temporaries_stay_linear(groups):
    """At 2**22 the body's temporaries stay under four buffers: no
    ``[groups, n]`` compare is built."""
    n = 1 << 22
    compiled = jax.jit(_body("grouped_count", groups)).lower(
        jax.ShapeDtypeStruct((n,), jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n * 4


# ---------------------------------------------------------------------------
# graph dwarfs: dense degree counts and hop max against the scatters
# ---------------------------------------------------------------------------

def _graph_reduction(reduction, vertices, dense):
    """A degree count or a hop max over the edges a buffer holds, through
    :mod:`statistic`'s router (``dense``) or the scatter onto zeros."""
    def f(x):
        src, dst = _edges_from_buffer(x, vertices)
        zeros = jnp.zeros((vertices,), jnp.float32)
        if reduction == "counts":
            return (grouped_counts(dst, vertices) if dense
                    else zeros.at[dst].add(1.0))
        vals = x[:src.shape[0]]                 # signed, so 0 wins some
        return (segment_max(vals, dst, vertices) if dense
                else zeros.at[dst].max(vals))
    return f


@pytest.mark.parametrize("block", [None, 300])
@pytest.mark.parametrize("reduction", ["counts", "max"])
@pytest.mark.parametrize("vertices", [64, 512, 4096, DENSE_GROUPS_MAX + 1])
def test_graph_reduction_matches_scatter(reduction, vertices, block, rng,
                                         monkeypatch):
    """The dense degree counts and hop max give the scatter's bits, lane
    alone and inside a vmap of 4; in one block, or in blocks of 300 ids
    whose last is cut short."""
    if block:
        monkeypatch.setattr(statistic, "_STEP_COMPARES", vertices * block)
    xs = jax.random.normal(rng, (4, GROUPED_N))
    dense = jax.jit(_graph_reduction(reduction, vertices, True))
    scatter = jax.jit(_graph_reduction(reduction, vertices, False))
    lanes = np.asarray(jax.jit(jax.vmap(
        _graph_reduction(reduction, vertices, True)))(xs))
    for i in range(xs.shape[0]):
        alone = np.asarray(dense(xs[i]))
        np.testing.assert_array_equal(alone, lanes[i])
        np.testing.assert_array_equal(alone, np.asarray(scatter(xs[i])))


def _scatter_form(name, x, p):
    """The graph dwarfs as written with scatters, before the dense
    reductions."""
    v = p.extra["vertices"]
    src, dst = _edges_from_buffer(x, v)
    zeros = jnp.zeros((v,), jnp.float32)
    if name == "graph_construction":
        return zeros.at[src].add(1.0)[src] + zeros.at[dst].add(1.0)[dst]
    if name == "graph_traversal":
        def hop(f, _):
            return jnp.maximum(f, zeros.at[dst].max(f[src])), ()
        frontier, _ = jax.lax.scan(hop, zeros.at[0].set(1.0), None,
                                   length=p.extra["hops"])
        return frontier[dst % v]
    deg = zeros.at[src].add(1.0)
    contrib = jnp.full((v,), 1.0 / v)[src] / jnp.maximum(deg[src], 1.0)
    return zeros.at[dst].add(contrib)[dst]


@pytest.mark.parametrize("name", ["graph_construction", "graph_traversal",
                                  "spmv"])
@pytest.mark.parametrize("vertices", [64, 512, 4096])
def test_graph_dwarf_equals_scatter_form(name, vertices, rng):
    """Each graph dwarf gives its scatter form's bits, lane alone and
    inside a vmap of 4."""
    comp = get_component(name)
    p = P.replace(data_size=GROUPED_N, extra={"vertices": vertices,
                                              "hops": 4})
    xs = jax.random.normal(rng, (4, GROUPED_N))
    run = jax.jit(lambda v: comp.apply(v, p, rng))
    lanes = np.asarray(jax.jit(jax.vmap(
        lambda v: comp.apply(v, p, rng)))(xs))
    before = jax.jit(lambda v: _scatter_form(name, v, p))
    for i in range(xs.shape[0]):
        alone = np.asarray(run(xs[i]))
        np.testing.assert_array_equal(alone, lanes[i])
        np.testing.assert_array_equal(alone, np.asarray(before(xs[i])))


#: degree counts and hop maxima each graph dwarf traces
_GRAPH_REDUCTIONS = {"graph_construction": (2, 0), "graph_traversal": (0, 1),
                     "spmv": (1, 0)}


@pytest.mark.parametrize("name,vertices,path,scatters", [
    ("graph_construction", 512, "dense", 0),
    ("graph_construction", DENSE_GROUPS_MAX + 1, "scatter", 2),
    ("graph_traversal", 512, "dense", 0),
    ("graph_traversal", DENSE_GROUPS_MAX + 1, "scatter", 1),
    ("spmv", 512, "dense", 1),                  # the rank sum's scatter
    ("spmv", DENSE_GROUPS_MAX + 1, "scatter", 2)])
def test_graph_lowering_path(name, vertices, path, scatters):
    """Up to the threshold no degree count or hop max lowers to a
    scatter, above it each keeps its scatter; the counters count the
    path each was traced through."""
    reset_grouped_stats()
    reset_segment_max_stats()
    comp = get_component(name)

    def body(v):
        p = ComponentParams(data_size=v.shape[0], chunk_size=64,
                            extra={"vertices": vertices, "hops": 2})
        return comp.apply(v, p, jax.random.PRNGKey(0))
    text = jax.jit(body).lower(
        jax.ShapeDtypeStruct((GROUPED_N,), jnp.float32)).compile().as_text()
    assert text.count(" scatter(") == scatters
    counts, maxima = _GRAPH_REDUCTIONS[name]
    dense = path == "dense"
    assert grouped_stats() == {"dense": counts * dense,
                               "scatter": counts * (not dense)}
    assert segment_max_stats() == {"dense": maxima * dense,
                                   "scatter": maxima * (not dense)}


def test_histogram_counts_match_bincount(rng):
    comp = get_component("histogram")
    x = jax.random.normal(rng, (GROUPED_N,))
    p = P.replace(data_size=GROUPED_N, extra={"bins": 8, "mix_rounds": 1})
    idx = np.asarray(_ids(x, 8, 1))
    expected = np.bincount(idx, minlength=8)[idx] / GROUPED_N
    np.testing.assert_array_equal(np.asarray(comp(x, p, rng)),
                                  expected.astype(np.float32))
