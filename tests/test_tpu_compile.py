"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached.

Each test lowers a kernel, a whole proxy plan or the stage megakernel at
the sizes the Table-3 proxies use, compiles it for one chip of a
``v5e:2x2`` topology, and checks that the kernel survived into the
executable (``tpu_custom_call``).  The TPU compiler refuses here what it
would refuse on the chip — a block that breaks the (8, 128) tiling, a
shape cast Mosaic cannot lay out, a conversion it lacks — and interpret
mode never does.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the suite runs in several workers.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import ProxySpec
from repro.core import schedule
from repro.core.dag import Edge, ProxyDAG
from repro.core.dwarfs import get_component
from repro.core.workloads import PROXY_SPECS, mega_chain
from repro.kernels.megakernel import CARRY_VMEM_BYTES

#: one proxy edge per kernel, at its proxy's shape: (proxy, edge index)
KERNEL_EDGES = {
    "sort_net": ("terasort", 2),            # quick_sort, 2048-wide rows
    "sort_net_padded": ("kmeans", 2),       # quick_sort, 64 -> 128 lanes
    "hash_mix": ("pagerank", 6),            # grouped_count
    "topk": ("lm_decode", 2),               # top_k, k=16
    "flash_attention": ("lm_decode", 0),    # GQA attention, 8 q / 2 kv heads
    "matmul": ("lm_train", 0),              # gemm_train fwd + bwd
    "matmul_scan": ("lm_decode", 1),        # scan_recurrent readout
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _chip_env(monkeypatch):
    """Trace with the Pallas backend and the interpreter off, as on the
    chip (on this CPU host both would otherwise resolve the other way)."""
    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _rng_spec(sharding):
    key = jax.random.PRNGKey(0)
    return _spec(key.shape, key.dtype, sharding)


def _compile_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("kernel", sorted(KERNEL_EDGES))
def test_component_kernel_compiles_for_v5e(kernel, one_chip, monkeypatch):
    proxy, idx = KERNEL_EDGES[kernel]
    e = Edge.from_json(PROXY_SPECS[proxy]["edges"][idx])
    comp = get_component(e.component)
    p = e.params.rounded()
    _chip_env(monkeypatch)
    text = _compile_text(lambda x, r: comp(x, p, r),
                         _spec((p.data_size,), jnp.float32, one_chip),
                         _rng_spec(one_chip))
    assert "tpu_custom_call" in text, kernel


def _plan_text(dag: ProxyDAG, plan, one_chip) -> str:
    dyn = jax.tree_util.tree_map(
        lambda v: _spec(jnp.shape(v), jnp.asarray(v).dtype, one_chip),
        dag.dynamic_params())
    return _compile_text(plan.build_parametric(), _rng_spec(one_chip), dyn)


@pytest.mark.parametrize("name", sorted(PROXY_SPECS))
def test_proxy_plan_compiles_for_v5e(name, one_chip, monkeypatch):
    dag = ProxySpec.from_json(PROXY_SPECS[name]).to_dag()
    # lower (which prices edge bodies on this host) before the chip's
    # backend settings apply; trace and compile under them
    plan = schedule.lower(dag, cache=False)
    _chip_env(monkeypatch)
    assert "tpu_custom_call" in _plan_text(dag, plan, one_chip), name


@pytest.mark.parametrize("size", [2048, CARRY_VMEM_BYTES // 4])
def test_megakernel_stage_compiles_for_v5e(size, one_chip, monkeypatch):
    """The megakernel benchmark's chain — sort, hash, top-k, min/max —
    fused into one stage: it must lower to the single kernel, with its
    trip counts in SMEM and its carry 2-D in VMEM, up to the largest
    carry the eligibility check admits."""
    dag = mega_chain(size)
    plan = schedule.lower(dag, threshold=1e30, cache=False)
    assert plan.mega_stage_count == 1
    _chip_env(monkeypatch)
    schedule.reset_mega_stats()
    text = _plan_text(dag, plan, one_chip)
    assert schedule.mega_stats()["mega"] == 1
    assert text.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("name", ["graph_construction", "graph_traversal"])
def test_graph_reductions_compile_dense_for_v5e(name, one_chip):
    """At the TeraSort cell's size (2**21 edges onto 512 vertices) the
    degree counts and the hop max lower to no scatter, and the blocked
    ``[vertices, block]`` compare is fused: the temporaries stay under
    four buffers."""
    n = 1 << 22
    comp = get_component(name)
    p = Edge.from_json(PROXY_SPECS["terasort"]["edges"][0]).params.replace(
        data_size=n, extra={"vertices": 512, "hops": 2})
    compiled = jax.jit(lambda x, r: comp(x, p, r)).lower(
        _spec((n,), jnp.float32, one_chip), _rng_spec(one_chip)).compile()
    assert " scatter(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n * 4
