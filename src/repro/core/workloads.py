"""The four BigDataBench originals (paper §2.4, Table 3) re-built in JAX,
plus their dwarf-DAG proxy benchmarks.

Each original follows the Hadoop job structure the paper profiles (input
partition → per-chunk map → intermediate materialization → shuffle/reduce);
the proxies are *declarative specs* (``PROXY_SPECS``, see
:mod:`repro.api.spec`) — DAG-like combinations of the Table-3 dwarf
components with initial weights from the paper (e.g. TeraSort = 70% sort,
10% sampling, 20% graph) — loaded through the versioned ProxySpec
round-trip rather than constructed inline.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..api.spec import SPEC_VERSION, ProxySpec
from ..data import generators as gen
from .dag import Edge, ProxyDAG
from .dwarfs import ComponentParams
from .proxy import ProxyBenchmark

# ---------------------------------------------------------------------------
# Scales: "full" sizes the original to seconds on one CPU core (the cluster
# analog), "tiny" is for tests.
# ---------------------------------------------------------------------------

SCALES = {
    "tiny": dict(terasort_n=1 << 12, kmeans_n=1 << 10, kmeans_d=16,
                 kmeans_k=4, kmeans_iters=2, pagerank_e=1 << 12,
                 pagerank_v=1 << 8, pagerank_iters=2, sift_b=2, sift_hw=32),
    "small": dict(terasort_n=1 << 18, kmeans_n=1 << 15, kmeans_d=32,
                  kmeans_k=16, kmeans_iters=3, pagerank_e=1 << 18,
                  pagerank_v=1 << 14, pagerank_iters=3, sift_b=4, sift_hw=128),
    "full": dict(terasort_n=1 << 23, kmeans_n=1 << 21, kmeans_d=64,
                 kmeans_k=64, kmeans_iters=10, pagerank_e=1 << 23,
                 pagerank_v=1 << 19, pagerank_iters=10, sift_b=16,
                 sift_hw=512),
}


@dataclasses.dataclass
class Workload:
    name: str
    pattern: str                           # paper's workload-pattern label
    make_inputs: Callable[[jax.Array, str], Tuple]
    step: Callable                         # jit-able job over the inputs
    table3_weights: Dict[str, float]       # paper's dwarf decomposition
    proxy_spec: Dict[str, Any]             # declarative Table-3 proxy spec

    def make_proxy(self) -> ProxyBenchmark:
        """Load the Table-3 proxy through the ProxySpec round-trip."""
        return ProxySpec.from_json(self.proxy_spec).to_benchmark()


def _edge(component: str, src, dst: str, *, weight: int = 1,
          data_size: int = 1 << 15, chunk_size: int = 256,
          parallelism: int = 1, **extra) -> Dict[str, Any]:
    """One declarative proxy-spec edge (plain JSON data)."""
    return {"component": component, "src": list(src), "dst": dst,
            "data_size": data_size, "chunk_size": chunk_size,
            "parallelism": parallelism, "weight": weight, "extra": extra}


# ---------------------------------------------------------------------------
# TeraSort — I/O intensive; dwarfs: sort, sampling, graph
# ---------------------------------------------------------------------------


def _terasort_inputs(rng: jax.Array, scale: str):
    n = SCALES[scale]["terasort_n"]
    keys, payload = gen.gen_records(rng, n)
    return keys, payload


def terasort_step(keys: jnp.ndarray, payload: jnp.ndarray):
    """sample -> range-partition -> shuffle -> per-partition sort."""
    n = keys.shape[0]
    n_part = 16
    # 1. interval sampling of keys (the TeraSort partitioner)
    sample = keys[:: max(1, n // 1024)]
    splitters = jnp.sort(sample)[:: max(1, sample.shape[0] // n_part)][1:n_part]
    # 2. partition id per record (range partitioner)
    pid = jnp.searchsorted(splitters, keys).astype(jnp.uint32)
    # 3. shuffle + sort: lexicographic (partition, key) — models the reduce
    #    phase where each reducer sorts its own range
    sorted_pid, sorted_keys, sorted_payload = jax.lax.sort(
        (pid, keys, payload), num_keys=2)
    # 4. per-partition boundary graph: offsets of each partition (degree count)
    counts = jnp.zeros((n_part,), jnp.int32).at[sorted_pid.astype(jnp.int32)].add(1)
    return sorted_keys, sorted_payload, counts


TERASORT_PROXY_SPEC: Dict[str, Any] = {
    "spec_version": SPEC_VERSION,
    "name": "proxy_terasort",
    "description": "Proxy TeraSort (Table 3: 70% sort / 10% sampling / "
                   "20% graph)",
    "stack": "hadoop",            # I/O intensive: host-spilled intermediates
    "scale": None,
    "sources": {"src": 1 << 15},
    "edges": [
        # sampling: 10%
        _edge("interval_sampling", ["src"], "sampled", chunk_size=2048,
              stride=4),
        _edge("random_sampling", ["src"], "sampled", chunk_size=2048,
              fraction=0.25),
        # sort: 70%
        _edge("quick_sort", ["sampled"], "sorted", weight=4, chunk_size=2048),
        _edge("merge_sort", ["sorted"], "merged", weight=2, chunk_size=2048),
        # graph: 20%
        _edge("graph_construction", ["merged"], "parts", chunk_size=2048,
              vertices=512),
        _edge("graph_traversal", ["parts"], "out", chunk_size=2048,
              vertices=512, hops=2),
    ],
    "sink": "out",
}


def terasort_proxy() -> ProxyBenchmark:
    return ProxySpec.from_json(TERASORT_PROXY_SPEC).to_benchmark()


# ---------------------------------------------------------------------------
# Kmeans — CPU intensive; dwarfs: matrix, sort, statistic
# ---------------------------------------------------------------------------


def _kmeans_inputs(rng: jax.Array, scale: str, sparsity: float = 0.0):
    s = SCALES[scale]
    k1, k2 = jax.random.split(rng)
    if sparsity > 0.0:
        idx, vals = gen.gen_sparse_csr(k1, s["kmeans_n"], s["kmeans_d"], sparsity)
        centers = gen.gen_matrix(k2, s["kmeans_k"], s["kmeans_d"])
        return idx, vals, centers
    x = gen.gen_matrix(k1, s["kmeans_n"], s["kmeans_d"])
    centers = gen.gen_matrix(k2, s["kmeans_k"], s["kmeans_d"])
    return x, centers


def kmeans_step(x: jnp.ndarray, centers: jnp.ndarray, iters: int = 3):
    """Lloyd iterations: distance matrix -> argmin -> grouped means."""

    def body(c, _):
        d2 = (jnp.sum(x * x, 1, keepdims=True) - 2.0 * x @ c.T
              + jnp.sum(c * c, 1))
        assign = jnp.argmin(d2, axis=1)                       # sort dwarf
        sums = jax.ops.segment_sum(x, assign, num_segments=c.shape[0])
        cnt = jax.ops.segment_sum(jnp.ones((x.shape[0],)), assign,
                                  num_segments=c.shape[0])
        newc = (sums / jnp.maximum(cnt, 1.0)[:, None]).astype(c.dtype)
        inertia = jnp.sum(jnp.min(d2, axis=1))
        return newc, inertia

    centers, inertia = jax.lax.scan(body, centers, None, length=iters)
    return centers, inertia


def kmeans_sparse_step(idx: jnp.ndarray, vals: jnp.ndarray,
                       centers: jnp.ndarray, iters: int = 3):
    """CSR Kmeans: gathered-dot distances (sparsity changes every shape)."""

    def body(c, _):
        # x.c^T for CSR rows: gather center cols then weighted sum
        gathered = c.T[idx]                       # (n, nnz, k)
        dots = jnp.einsum("ne,nek->nk", vals, gathered)
        d2 = jnp.sum(vals * vals, 1, keepdims=True) - 2.0 * dots \
            + jnp.sum(c * c, 1)
        assign = jnp.argmin(d2, axis=1)
        # grouped mean in the sparse pattern's dense footprint
        dense = jnp.zeros((vals.shape[0], c.shape[1])
                          ).at[jnp.arange(vals.shape[0])[:, None], idx].add(vals)
        sums = jax.ops.segment_sum(dense, assign, num_segments=c.shape[0])
        cnt = jax.ops.segment_sum(jnp.ones((vals.shape[0],)), assign,
                                  num_segments=c.shape[0])
        newc = sums / jnp.maximum(cnt, 1.0)[:, None]
        return newc, jnp.sum(jnp.min(d2, axis=1))

    centers, inertia = jax.lax.scan(body, centers, None, length=iters)
    return centers, inertia


KMEANS_PROXY_SPEC: Dict[str, Any] = {
    "spec_version": SPEC_VERSION,
    "name": "proxy_kmeans",
    "description": "Proxy Kmeans (Table 3: matrix / sort / basic statistic)",
    "stack": "openmp",            # CPU intensive: single-process jit
    "scale": None,
    "sources": {"src": 1 << 15},
    "edges": [
        _edge("euclidean_distance", ["src"], "dist", weight=4, chunk_size=64,
              centers=16),
        _edge("cosine_distance", ["src"], "dist", chunk_size=64, centers=16),
        _edge("quick_sort", ["dist"], "assign", chunk_size=64),
        _edge("count_average", ["assign"], "stats", weight=2, chunk_size=64),
        _edge("grouped_count", ["stats"], "out", chunk_size=64, groups=16),
    ],
    "sink": "out",
}


def kmeans_proxy() -> ProxyBenchmark:
    return ProxySpec.from_json(KMEANS_PROXY_SPEC).to_benchmark()


# ---------------------------------------------------------------------------
# PageRank — hybrid; dwarfs: matrix, sort, statistic (paper Table 3)
# ---------------------------------------------------------------------------


def _pagerank_inputs(rng: jax.Array, scale: str):
    s = SCALES[scale]
    src, dst = gen.gen_graph(rng, s["pagerank_e"], s["pagerank_v"])
    return src, dst


def pagerank_step(src: jnp.ndarray, dst: jnp.ndarray, n_vertices: int,
                  iters: int = 5):
    deg = jnp.zeros((n_vertices,), jnp.float32).at[src].add(1.0)

    def body(rank, _):
        contrib = rank[src] / jnp.maximum(deg[src], 1.0)      # matrix row-norm
        nxt = jnp.zeros((n_vertices,), jnp.float32).at[dst].add(contrib)
        nxt = 0.15 / n_vertices + 0.85 * nxt
        return nxt, jnp.max(jnp.abs(nxt - rank))              # min/max calc

    rank0 = jnp.full((n_vertices,), 1.0 / n_vertices)
    rank, deltas = jax.lax.scan(body, rank0, None, length=iters)
    top_vals, top_idx = jax.lax.top_k(rank, 16)               # sort dwarf
    return rank, top_vals, deltas


PAGERANK_PROXY_SPEC: Dict[str, Any] = {
    "spec_version": SPEC_VERSION,
    "name": "proxy_pagerank",
    "description": "Proxy PageRank (Table 3: matrix / sort / basic "
                   "statistic)",
    "stack": "spark",             # hybrid: global-view, memory-resident
    "scale": None,
    "sources": {"src": 1 << 15},
    "edges": [
        _edge("matrix_construction", ["src"], "mat"),
        _edge("matrix_multiplication", ["mat"], "mm"),
        _edge("spmv", ["src"], "mm", weight=3, vertices=4096),
        _edge("graph_construction", ["mm"], "deg", vertices=4096),
        _edge("quick_sort", ["deg"], "ranked"),
        _edge("min_max", ["ranked"], "norm"),
        _edge("grouped_count", ["norm"], "out", groups=256),
    ],
    "sink": "out",
}


def pagerank_proxy() -> ProxyBenchmark:
    return ProxySpec.from_json(PAGERANK_PROXY_SPEC).to_benchmark()


# ---------------------------------------------------------------------------
# SIFT — CPU+memory intensive; dwarfs: matrix, sort, sampling, transform, stat
# ---------------------------------------------------------------------------


def _sift_inputs(rng: jax.Array, scale: str):
    s = SCALES[scale]
    return (gen.gen_images(rng, s["sift_b"], s["sift_hw"], s["sift_hw"]),)


def sift_step(images: jnp.ndarray):
    """FFT gaussian pyramid -> DoG -> extrema -> orientation histograms."""
    b, h, w = images.shape
    spec = jnp.fft.rfft2(images)                              # transform
    fy = jnp.fft.fftfreq(h)[:, None]
    fx = jnp.fft.rfftfreq(w)[None, :]
    freq2 = fy * fy + fx * fx
    octaves = []
    for sigma in (1.0, 2.0, 4.0, 8.0):
        g = jnp.exp(-2.0 * (jnp.pi ** 2) * freq2 * sigma ** 2)
        octaves.append(jnp.fft.irfft2(spec * g, s=(h, w)))
    pyr = jnp.stack(octaves, 1)                               # (b, 4, h, w)
    dog = pyr[:, 1:] - pyr[:, :-1]                            # (b, 3, h, w)
    # local extrema: 3x3 max-pool compare
    mx = jax.lax.reduce_window(dog, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                               (1, 1, 1, 1), "SAME")
    is_max = (dog >= mx).astype(jnp.float32)                  # set/compare
    # gradients + orientation histogram (8 bins)
    gy = dog[:, :, 1:, :] - dog[:, :, :-1, :]
    gx = dog[:, :, :, 1:] - dog[:, :, :, :-1]
    gy, gx = gy[:, :, :, 1:], gx[:, :, 1:, :]
    mag = jnp.sqrt(gy * gy + gx * gx + 1e-12)
    ang = jnp.arctan2(gy, gx)
    bins = ((ang + jnp.pi) / (2 * jnp.pi) * 8).astype(jnp.int32) % 8
    hist = jax.ops.segment_sum(mag.reshape(-1), bins.reshape(-1),
                               num_segments=8)                # statistic
    # descriptors: sampled patches x random projection (matrix)
    patches = dog[:, :, ::8, ::8].reshape(b, -1)              # interval sample
    proj = jax.random.normal(jax.random.PRNGKey(7), (patches.shape[1], 64))
    desc = patches @ proj
    top_vals, _ = jax.lax.top_k(desc.reshape(b, -1), 32)      # sort
    return desc, hist, is_max.sum(), top_vals


SIFT_PROXY_SPEC: Dict[str, Any] = {
    "spec_version": SPEC_VERSION,
    "name": "proxy_sift",
    "description": "Proxy SIFT (Table 3: matrix / sort / sampling / "
                   "transform / statistic)",
    "stack": "mpi",               # CPU+memory intensive: explicit SPMD
    "scale": None,
    "sources": {"src": 1 << 15},
    "edges": [
        _edge("fft", ["src"], "freq", weight=3),
        _edge("matrix_construction", ["freq"], "mat"),
        _edge("matrix_multiplication", ["mat"], "mm", weight=2),
        _edge("interval_sampling", ["mm"], "sampled", stride=8),
        _edge("quick_sort", ["sampled"], "sorted"),
        _edge("min_max", ["sorted"], "norm"),
        _edge("histogram", ["norm"], "out", bins=8),
    ],
    "sink": "out",
}


def sift_proxy() -> ProxyBenchmark:
    return ProxySpec.from_json(SIFT_PROXY_SPEC).to_benchmark()


# ---------------------------------------------------------------------------
# AI proxies (Data-Dwarfs extension) — LM training and decode-serving as
# dwarf DAGs over the AI components (core/dwarfs/ai.py).  These have no
# "original" Hadoop-style step function; their reference targets are the
# full-model dry-run cells benchmarks/lm_proxy.py profiles.
# ---------------------------------------------------------------------------

LM_TRAIN_PROXY_SPEC: Dict[str, Any] = {
    "spec_version": SPEC_VERSION,
    "name": "proxy_lm_train",
    "description": "Proxy LM training step (AI dwarfs: gemm fwd+bwd, "
                   "GQA attention, loss statistics)",
    "stack": "mpi",               # training is SPMD: explicit data parallel
    "scale": None,
    "sources": {"tokens": 1 << 15},
    "edges": [
        # in/out projections: dense-layer GEMM triple, 2 optimizer rounds
        _edge("gemm_train", ["tokens"], "h0", weight=2, rounds=2),
        # GQA flash attention over the residual stream
        _edge("attention", ["h0"], "attn", weight=2, seq_len=128, heads=4,
              kv_heads=2),
        # MLP block dominates train-step flops
        _edge("gemm_train", ["attn"], "mlp", weight=4, rounds=2),
        # loss reduction / metrics
        _edge("count_average", ["mlp"], "out"),
    ],
    "sink": "out",
}


def lm_train_proxy() -> ProxyBenchmark:
    return ProxySpec.from_json(LM_TRAIN_PROXY_SPEC).to_benchmark()


LM_DECODE_PROXY_SPEC: Dict[str, Any] = {
    "spec_version": SPEC_VERSION,
    "name": "proxy_lm_decode",
    "description": "Proxy LM decode step (AI dwarfs: MQA-style attention, "
                   "recurrent scan, top-k sampling)",
    "stack": "openmp",            # latency path: single-process jit
    "scale": None,
    "sources": {"tokens": 1 << 14},
    "edges": [
        # KV-cache-heavy attention: many query heads per KV head
        _edge("attention", ["tokens"], "attn", weight=2, data_size=1 << 14,
              chunk_size=128, seq_len=256, heads=8, kv_heads=2),
        # hybrid-decode recurrence (SSM scan + readout projection)
        _edge("scan_recurrent", ["attn"], "ssm", data_size=1 << 14,
              chunk_size=128, state=8, rounds=1),
        # sampling the next token: top-k over logits
        _edge("top_k", ["ssm"], "out", data_size=1 << 14, chunk_size=128,
              k=16),
    ],
    "sink": "out",
}


def lm_decode_proxy() -> ProxyBenchmark:
    return ProxySpec.from_json(LM_DECODE_PROXY_SPEC).to_benchmark()


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def _kmeans_io(scale):  # default dense
    return _kmeans_inputs(jax.random.PRNGKey(0), scale)


#: workload name -> declarative Table-3 proxy spec (the emit/load surface)
PROXY_SPECS: Dict[str, Dict[str, Any]] = {
    "terasort": TERASORT_PROXY_SPEC,
    "kmeans": KMEANS_PROXY_SPEC,
    "pagerank": PAGERANK_PROXY_SPEC,
    "sift": SIFT_PROXY_SPEC,
    "lm_train": LM_TRAIN_PROXY_SPEC,
    "lm_decode": LM_DECODE_PROXY_SPEC,
}


def mega_chain(size: int = 2048, chunk: int = 128) -> ProxyDAG:
    """A private linear chain whose members all have registered megakernel
    segment bodies (sort, hash, top-k, min/max): it fuses into one
    mega-eligible stage while its carry fits VMEM."""
    p = lambda w, **e: ComponentParams(data_size=size, chunk_size=chunk,
                                       weight=w, extra=e)
    return ProxyDAG(
        "mega_chain", {"src": size},
        [Edge("quick_sort", ["src"], "a", p(2)),
         Edge("hash", ["a"], "b", p(3, rounds=2)),
         Edge("top_k", ["b"], "c", p(2, k=8)),
         Edge("min_max", ["c"], "out", p(1))],
        "out")


def seed_structures(names: Optional[Sequence[str]] = None) -> List["ProxyDAG"]:
    """Seed pool for the structural search: every named Table-3 proxy's
    DAG, loaded through the versioned ProxySpec round-trip (so a machine
    mutation always starts from the same structures a human would).
    ``names`` restricts/reorders the pool; default is every
    ``PROXY_SPECS`` entry in sorted order."""
    picked = sorted(PROXY_SPECS) if names is None else list(names)
    return [ProxySpec.from_json(PROXY_SPECS[n]).to_benchmark().dag
            for n in picked]


def seed_components(names: Optional[Sequence[str]] = None) -> List[str]:
    """The dwarf components appearing in the Table-3 proxies — the
    default mutation-component pool a structural search draws from when
    the caller does not widen it."""
    picked = sorted(PROXY_SPECS) if names is None else list(names)
    return sorted({e["component"]
                   for n in picked for e in PROXY_SPECS[n]["edges"]})


WORKLOADS: Dict[str, Workload] = {
    "terasort": Workload(
        "terasort", "io-intensive", _terasort_inputs,
        terasort_step,
        {"sort": 0.7, "sampling": 0.1, "graph": 0.2},
        TERASORT_PROXY_SPEC),
    "kmeans": Workload(
        "kmeans", "cpu-intensive", lambda r, s: _kmeans_inputs(r, s),
        lambda x, c: kmeans_step(x, c, 3),
        {"matrix": 0.6, "sort": 0.2, "statistic": 0.2},
        KMEANS_PROXY_SPEC),
    "pagerank": Workload(
        "pagerank", "hybrid", _pagerank_inputs,
        None,  # bound per-scale below (needs n_vertices)
        # Table 1 lists PageRank as Matrix+Graph+Sort; our original realizes
        # the sparse matrix product as gather/segment-sum (graph dwarf)
        {"graph": 0.45, "matrix": 0.25, "sort": 0.15, "statistic": 0.15},
        PAGERANK_PROXY_SPEC),
    "sift": Workload(
        "sift", "cpu-memory-intensive", _sift_inputs,
        sift_step,
        {"matrix": 0.35, "transform": 0.25, "sampling": 0.1, "sort": 0.15,
         "statistic": 0.15},
        SIFT_PROXY_SPEC),
}


def workload_step_fn(name: str, scale: str):
    """Returns (fn, args) ready for characterize()/execution."""
    w = WORKLOADS[name]
    rng = jax.random.PRNGKey(0)
    args = w.make_inputs(rng, scale)
    s = SCALES[scale]
    if name == "pagerank":
        fn = lambda src, dst: pagerank_step(src, dst, s["pagerank_v"],
                                            s["pagerank_iters"])
    elif name == "kmeans":
        fn = lambda x, c: kmeans_step(x, c, s["kmeans_iters"])
    else:
        fn = w.step
    return fn, args


def proxy_fingerprint(name: str):
    """Fingerprint a Table-3 proxy (any ``PROXY_SPECS`` key) through the
    compositional cost model — zero compiles once its edges are cached.

    This is the *proxy side* of the distillation loop: the vector a
    perfectly-tuned synthesis should land on.  Compare
    :func:`workload_fingerprint`, which measures the original."""
    from .engine import fingerprint
    if name not in PROXY_SPECS:
        raise KeyError(f"unknown proxy {name!r}; known: "
                       f"{sorted(PROXY_SPECS)}")
    dag = ProxySpec.from_json(PROXY_SPECS[name]).to_dag()
    return fingerprint(dag, name=name)


def workload_fingerprint(name: str, scale: str = "tiny"):
    """Fingerprint an *original* workload implementation — the measured
    target the paper distills proxies from.

    Big-data originals (``WORKLOADS``) are lowered through HLO cost
    analysis at ``scale`` via :func:`workload_step_fn`; the AI proxies
    (``lm_train``/``lm_decode``), which have no separate original here,
    fall back to their spec DAG's compositional fingerprint."""
    from .engine import fingerprint
    if name in WORKLOADS:
        fn, args = workload_step_fn(name, scale)
        return fingerprint(fn, *args, name=name)
    if name in PROXY_SPECS:
        return proxy_fingerprint(name)
    raise KeyError(f"unknown workload {name!r}; known: "
                   f"{sorted(set(WORKLOADS) | set(PROXY_SPECS))}")
