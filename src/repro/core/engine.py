"""Compile-once/run-many measurement engine for DAG proxies.

The auto-tuner (paper §2.3) re-measures the proxy after every parameter
probe and every adjustment step.  The seed paid a full XLA lower+compile
per measurement — and, because weights were Python-unrolled, that compile
scaled with total DAG weight.  This engine makes the run-many regime cheap
by splitting measurement along the same static/dynamic boundary as
``ProxyDAG``:

* **Structural metrics** (instruction mix, arithmetic intensity, …) come
  from a *compositional* cost model: each edge's single-repeat body is
  lowered, compiled and HLO-analyzed **once per static structure key** and
  cached process-wide; a proxy's report is then

      sources + Σ_edge weight_e × body_e + finalize

  so stepping any dynamic param (weight, shape-free extras) is pure
  arithmetic — zero compiles, zero traces.  Changing a shape-affecting
  param recompiles only the touched edge.
* **Rate metrics** (mips / flop_rate / mem_bw analogs, ``execute=True``)
  additionally time a real execution through a cached parametric
  executable (one compile per DAG structure key; dynamic params are jitted
  arguments, so weight sweeps re-run the same compiled program).

``stats()`` exposes compile/trace counters so tests and benchmarks can
assert the no-retrace contract.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .cachetools import LOCK
from .dag import _INT_DYNAMIC, ProxyDAG, _init_sources, _terminals
from .dwarfs import get_component
from .dwarfs.base import fit_buffer
from .metrics import CostReport, analyze_hlo_text, metric_vector
from .pool import get_pool

# process-wide caches: structure keys are value-hashable, so clones and
# re-built DAGs with identical structure share entries.  Report caches hold
# small dataclasses and can grow large; the executable cache retains
# compiled XLA programs, so it is kept tight.  All three register as
# domains of the process-wide ExecutablePool — one admission/eviction
# policy with the stack and plan caches — while the dicts themselves stay
# module-level (the pool owns bookkeeping, not values).
_BODY_CACHE: Dict[Tuple, CostReport] = {}
_PIECE_CACHE: Dict[Tuple, CostReport] = {}
_EXEC_CACHE: Dict[Tuple, Callable] = {}

_REPORT_CACHE_CAP = 4096
_EXEC_CACHE_CAP = 128

_BODY_DOM = get_pool().register("engine:body", _BODY_CACHE, kind="report",
                                cap=_REPORT_CACHE_CAP)
_PIECE_DOM = get_pool().register("engine:piece", _PIECE_CACHE, kind="report",
                                 cap=_REPORT_CACHE_CAP)
_EXEC_DOM = get_pool().register("engine:exec", _EXEC_CACHE,
                                kind="executable", cap=_EXEC_CACHE_CAP)

#: ``analyze_s`` — seconds spent in :func:`_analyze` (lowering, compiling
#: and reading a body's HLO for its cost)
_STATS = {"compiles": 0, "traces": 0, "hits": 0, "exec_compiles": 0,
          "analyze_s": 0.0}


def stats() -> Dict[str, float]:
    """Counters of engine compile/trace activity (monotonic)."""
    return dict(_STATS)


def reset_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


def clear_caches() -> None:
    """Drop every cached report/executable (tests and benchmarks use this
    to measure cold-vs-warm behaviour).  Clears through the pool so the
    eviction-order bookkeeping stays coherent with the dicts."""
    pool = get_pool()
    for name in ("engine:body", "engine:piece", "engine:exec"):
        pool.clear(name)


def _analyze(fn: Callable, args: Tuple) -> CostReport:
    """Lower+compile ``fn`` (abstract args are fine) and analyze its HLO."""
    _STATS["compiles"] += 1
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("engine.analyze"):
        compiled = jax.jit(fn).lower(*args).compile()
        rep = analyze_hlo_text(compiled.as_text())
    _STATS["analyze_s"] += time.perf_counter() - t0
    return rep


def _rng_spec() -> jax.Array:
    return jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# compositional pieces
# ---------------------------------------------------------------------------


def _body_key(e) -> Tuple:
    """Body-report cache key.  Unlike the *executable* caches (where dynamic
    extras are jitted arguments), the analyzed body HLO bakes the current
    dynamic-extra values in (e.g. hash ``rounds`` sets a loop trip count),
    so the report is only valid for those values — weight alone stays
    factored out as the linear multiplier."""
    p = e.params.rounded()
    dyn_vals = tuple(sorted(
        (k, int(round(float(p.extra[k]))) if k in _INT_DYNAMIC
         else float(p.extra[k]))
        for k in e.dynamic_fields() if k != "weight"))
    return (e.structure_key(), dyn_vals)


def _body_report(e) -> CostReport:
    """Cost of ONE repeat of edge ``e`` (the fori_loop body): component
    application + the fit-back glue, exactly as ``dag._edge_out`` traces it."""
    key = _body_key(e)
    with LOCK:
        rep = _BODY_CACHE.get(key)
        if rep is not None:
            _STATS["hits"] += 1
            _BODY_DOM.stats["hits"] += 1
            return rep
        _BODY_DOM.stats["misses"] += 1
        p = e.params.rounded()
        comp = get_component(e.component)

        def body(x, rng):
            return fit_buffer(comp(x, p, jax.random.fold_in(rng, 0)),
                              p.data_size)

        x_spec = jax.ShapeDtypeStruct((p.data_size,), jnp.float32)
        rep = _analyze(body, (x_spec, _rng_spec()))
        return get_pool().put(_BODY_DOM, key, rep)


def _piece_report(key: Tuple, make: Callable[[], CostReport]) -> CostReport:
    with LOCK:
        rep = _PIECE_CACHE.get(key)
        if rep is not None:
            _STATS["hits"] += 1
            _PIECE_DOM.stats["hits"] += 1
            return rep
        _PIECE_DOM.stats["misses"] += 1
        return get_pool().put(_PIECE_DOM, key, make())


def _sources_report(sources: Tuple[Tuple[str, int], ...]) -> CostReport:
    return _piece_report(
        ("sources", sources),
        lambda: _analyze(lambda rng: _init_sources(dict(sources), rng),
                         (_rng_spec(),)))


def _finalize_report(n: int) -> CostReport:
    return _piece_report(
        ("finalize", n),
        lambda: _analyze(lambda x: jnp.sum(x),
                         (jax.ShapeDtypeStruct((max(n, 1),), jnp.float32),)))


def _sink_sizes_from(sources: Dict[str, int], edges, sink) -> int:
    """Element count feeding the final reduction(s)."""
    sizes = {name: int(n) for name, n in sources.items()}
    for e in edges:
        sizes[e.dst] = e.params.rounded().data_size
    if sink is not None:
        return sizes.get(sink, 1)
    return sum(sizes.get(t, 1) for t in _terminals(list(edges)))


def _sink_sizes(dag: ProxyDAG) -> int:
    return _sink_sizes_from(dag.sources, dag.edges, dag.sink)


def _assemble_report(sources: Dict[str, int], edges, sink) -> CostReport:
    total = CostReport()
    total.add(_sources_report(tuple(sorted(sources.items()))))
    for e in edges:
        w = float(e.params.rounded().weight)
        if w > 0:
            total.add(_body_report(e), mult=w)
    total.add(_finalize_report(_sink_sizes_from(sources, edges, sink)))
    return total


def structural_report(dag: ProxyDAG) -> CostReport:
    """Whole-proxy cost report assembled from cached per-edge pieces."""
    return _assemble_report(dag.sources, dag.edges, dag.sink)


def measure_plan(plan, host_bytes: float = 0.0) -> Dict[str, float]:
    """The compositional metric vector straight from an
    :class:`~repro.core.schedule.ExecutionPlan` — no ProxyDAG rebuild, no
    stack, no execution.  The plan's rounded lowering-time edges carry
    everything the cost model needs, so a structural search can score
    candidate plans as pure IR."""
    return metric_vector(
        _assemble_report(plan.sources, plan.edges, plan.sink),
        host_bytes=host_bytes)


# ---------------------------------------------------------------------------
# population measurement (vectorized compositional model)
# ---------------------------------------------------------------------------

#: the CostReport channels :func:`repro.core.metrics.metric_vector` reads,
#: flattened so population reports assemble as numpy linear algebra
_BASIS_FIELDS = ("flops", "vpu_ops", "bytes_accessed", "rng_elems",
                 "sort_elems", "fft_elems", "gather_elems", "reduce_elems",
                 "logic_elems", "compare_elems", "elementwise_elems",
                 "attention_flops")


def _report_to_vec(rep: CostReport) -> np.ndarray:
    return np.array([getattr(rep, f) for f in _BASIS_FIELDS]
                    + [rep.total_collective_bytes], dtype=np.float64)


def _vec_to_report(v: np.ndarray) -> CostReport:
    rep = CostReport(**{f: float(v[i]) for i, f in enumerate(_BASIS_FIELDS)})
    if v[-1]:
        rep.collective_bytes["all"] = float(v[-1])
    return rep


def _edge_with_extras(e, fields: Tuple[str, ...], values: Tuple) -> Any:
    work = dataclasses.replace(
        e, params=e.params.replace(extra=dict(e.params.extra)))
    for f, v in zip(fields, values):
        work.params.extra[f] = v
    return work


class PopulationScorer:
    """Precomputed flat-basis scorer for populations sharing one DAG
    structure — the :class:`~repro.core.autotune.PopulationTuner` hot path.

    Exploits the compositional model's linearity in the weights: at
    construction each edge's single-repeat body report is fetched once
    (per distinct dynamic-extra setting, lazily) and flattened to a
    channel vector, so every subsequent ``score(matrix)`` assembles all
    ``n`` candidates as

        M = const + W @ B          (numpy, one row per candidate)

    instead of ``n`` independent ``measure()`` walks.  Zero executable
    traces ever; body compiles only for dynamic-extra values never
    analyzed before (identical to what a single measurement at those
    values costs).  Candidate rows must differ from the construction-time
    parameters only in *dynamic* leaves — static leaves define the shared
    structure; rebuild the scorer after a structural step.
    """

    def __init__(self, dag: ProxyDAG, space, host_bytes: float = 0.0):
        self.host_bytes = host_bytes
        self._dag = dag
        self._space = space
        self._n_leaves = len(space)
        self._static = ~space.dynamic_mask()
        self._static_vals = space.values(dag)[self._static]
        self._static_names = [n for n, s in zip(space.names, self._static)
                              if s]
        const = _report_to_vec(
            _sources_report(tuple(sorted(dag.sources.items()))))
        const += _report_to_vec(_finalize_report(_sink_sizes(dag)))
        self._const = const
        # per edge: (weight column, dynamic-extra columns/fields, body
        # vector for extra-free edges, lazy per-extra-value vector cache)
        self._edges = []
        for ei, e in enumerate(dag.edges):
            prefix = f"e{ei}.{e.component}"
            extra_fields = tuple(f for f in e.dynamic_fields()
                                 if f != "weight")
            self._edges.append({
                "edge": e,
                "w_idx": space.index_of(f"{prefix}.weight"),
                "extra_fields": extra_fields,
                "extra_idx": [space.index_of(f"{prefix}.{f}")
                              for f in extra_fields],
                "body": (None if extra_fields
                         else _report_to_vec(_body_report(e))),
                "by_extras": {},
            })

    def _body_vec(self, info: Dict, values: Tuple) -> np.ndarray:
        vec = info["by_extras"].get(values)
        if vec is None:
            vec = _report_to_vec(_body_report(
                _edge_with_extras(info["edge"], info["extra_fields"],
                                  values)))
            info["by_extras"][values] = vec
        return vec

    def score(self, matrix) -> List[Dict[str, float]]:
        """Metric dicts (``measure(execute=False)``-identical keys) for
        every row of a ``(n, len(space))`` candidate matrix."""
        matrix = np.asarray(matrix, np.float64)
        if matrix.ndim != 2 or matrix.shape[1] != self._n_leaves:
            raise ValueError(f"expected a (n, {self._n_leaves}) candidate "
                             f"matrix, got shape {matrix.shape}")
        n = matrix.shape[0]
        if n and (matrix[:, self._static] != self._static_vals).any():
            bad = np.nonzero((matrix[:, self._static]
                              != self._static_vals).any(axis=0))[0]
            names = [self._static_names[b] for b in bad[:4]]
            raise ValueError(
                f"population rows change static leaves {names}; a "
                f"population shares one structure — rebuild the scorer "
                f"per structure instead")
        total = np.tile(self._const, (n, 1))
        for info in self._edges:
            w_col = np.round(matrix[:, info["w_idx"]])
            if info["body"] is not None:
                total += np.outer(w_col, info["body"])
                continue
            # dynamic extras bake into the body HLO: one vector per
            # distinct value tuple present in the population
            vals = np.stack([matrix[:, i] for i in info["extra_idx"]], axis=1)
            for row in np.unique(vals, axis=0):
                mask = (vals == row).all(axis=1)
                total[mask] += np.outer(
                    w_col[mask], self._body_vec(info, tuple(row)))
        return [metric_vector(_vec_to_report(total[i]),
                              host_bytes=self.host_bytes) for i in range(n)]

    __call__ = score

    # -- weight-stratified (per-bucket) view --------------------------------

    def bucket_schedule(self, matrix, bucket_size: Optional[int] = None):
        """The population's weight-stratified
        :class:`~repro.core.schedule.BucketSchedule`, computed with the
        same per-edge body costs the execution plan uses — so the scorer's
        strata line up exactly with the strata the stacks execute, and the
        tuner can spend its candidate budget where the weight mass is."""
        from .schedule import (make_bucket_schedule, resolve_bucket_size,
                               _edge_body_cost)
        matrix = np.asarray(matrix, np.float64)
        n = matrix.shape[0]
        costs = np.zeros(n, np.float64)
        trips = np.zeros(n, np.float64)
        for info in self._edges:
            w = np.round(np.maximum(matrix[:, info["w_idx"]], 0.0))
            costs += w * max(_edge_body_cost(info["edge"]), 1.0)
            trips += w
        if bucket_size is None:
            bucket_size = resolve_bucket_size(n)
        return make_bucket_schedule(costs, trips, bucket_size)

    def score_bucketed(self, matrix, bucket_size: Optional[int] = None):
        """``(metrics, schedule)``: metric dicts in the caller's candidate
        order plus the schedule that stratifies them — per-bucket scoring
        for the population tuner (scores are bucket-composition
        independent; the schedule carries the per-bucket mass/trip
        accounting)."""
        return self.score(matrix), self.bucket_schedule(matrix, bucket_size)


def measure_population(dag: ProxyDAG, space, matrix,
                       host_bytes: float = 0.0) -> List[Dict[str, float]]:
    """One-shot :class:`PopulationScorer`: metric vectors for a whole
    population of candidate vectors sharing ``dag``'s structure."""
    return PopulationScorer(dag, space, host_bytes=host_bytes)(matrix)


# ---------------------------------------------------------------------------
# structure measurement (mutation-delta scoring)
# ---------------------------------------------------------------------------


def _edge_vec(e) -> np.ndarray:
    """One edge's weighted contribution to the flat channel basis."""
    w = float(e.params.rounded().weight)
    if w <= 0:
        return np.zeros(len(_BASIS_FIELDS) + 1, np.float64)
    return w * _report_to_vec(_body_report(e))


def _dag_score_key(dag: ProxyDAG) -> Tuple:
    """Cache key of a dag's *compositional score*: the canonical structure
    plus every dynamic value (weights, extras) — two dags share a score
    vector only when they are relabelings with identical parameters."""
    dyn = tuple(
        tuple(sorted(
            (k, int(round(float(v))) if k in _INT_DYNAMIC else float(v))
            for k, v in (
                (f, e.params.rounded().weight if f == "weight"
                 else e.params.rounded().extra[f])
                for f in e.dynamic_fields())))
        for e in dag.edges)
    return (dag.canonical_structure_key(), dyn)


class StructureScorer:
    """Compositional scorer over *structures* — the outer-loop counterpart
    of :class:`PopulationScorer` (which scores weight candidates of one
    structure).

    Whole-structure reports are cached as flat channel vectors keyed on
    the canonical structure *plus* every dynamic value (weights change the
    score but not the structure), and a mutated child scores as a
    **delta** from its parent's cached vector:

        child = parent - Σ removed (weight × body) + Σ added (weight × body)
                ± the finalize-size correction

    so scoring ``m`` mutations of one parent costs ``O(Σ |edit|)`` cached
    body lookups rather than ``m`` full DAG walks — and *zero* compiles or
    traces when every (component, shape) involved has already been
    analyzed.  ``new_compiles`` counts the body analyses a scoring run did
    trigger (a structure introducing a never-profiled component pays
    exactly one)."""

    def __init__(self, host_bytes: float = 0.0):
        self.host_bytes = host_bytes
        self._vecs: Dict[Tuple, np.ndarray] = {}
        self._compiles0 = _STATS["compiles"]

    @property
    def new_compiles(self) -> int:
        """Body analyses triggered since this scorer was constructed."""
        return _STATS["compiles"] - self._compiles0

    def structures_cached(self) -> int:
        return len(self._vecs)

    def _vec(self, dag: ProxyDAG) -> np.ndarray:
        key = _dag_score_key(dag)
        vec = self._vecs.get(key)
        if vec is None:
            vec = _report_to_vec(structural_report(dag))
            self._vecs[key] = vec
        return vec

    def score(self, dag: ProxyDAG) -> Dict[str, float]:
        """Metric vector of ``dag`` (``measure(execute=False)``-identical
        keys), cached per canonical structure."""
        return metric_vector(_vec_to_report(self._vec(dag).copy()),
                             host_bytes=self.host_bytes)

    def score_child(self, parent: ProxyDAG, child: ProxyDAG,
                    removed: Sequence = (), added: Sequence = ()
                    ) -> Dict[str, float]:
        """Score ``child`` as a mutation delta from ``parent``.

        ``removed`` are the *parent* edges the mutation dropped and
        ``added`` the edges it introduced (a rewired-only edge — src
        renames — appears in neither: node names do not enter the body
        cost).  Falls back to a full assembly when the mutation touched
        the sources.  The resulting vector is cached under the child's
        canonical key, so it can seed further delta scoring."""
        key = _dag_score_key(child)
        vec = self._vecs.get(key)
        if vec is None:
            if dict(parent.sources) != dict(child.sources):
                return self.score(child)
            vec = self._vec(parent).copy()
            for e in removed:
                vec -= _edge_vec(e)
            for e in added:
                vec += _edge_vec(e)
            fin_p = _sink_sizes(parent)
            fin_c = _sink_sizes(child)
            if fin_p != fin_c:
                vec -= _report_to_vec(_finalize_report(fin_p))
                vec += _report_to_vec(_finalize_report(fin_c))
            self._vecs[key] = vec
        return metric_vector(_vec_to_report(vec.copy()),
                             host_bytes=self.host_bytes)


# ---------------------------------------------------------------------------
# cached execution (rate metrics)
# ---------------------------------------------------------------------------


def executable(dag: ProxyDAG) -> Callable[[jax.Array], Any]:
    """Cached compiled runner for ``dag``: ``fn(rng) -> scalar`` binding the
    dag's *current* dynamic params as jitted arguments.  One compile per
    *canonical* structure key (stable under node relabeling, so
    machine-generated isomorphic structures share the compile); stepping
    weights/extras re-uses the executable."""
    key = dag.canonical_structure_key()
    with LOCK:
        jfn = _EXEC_CACHE.get(key)
        if jfn is None:
            _STATS["exec_compiles"] += 1
            _EXEC_DOM.stats["misses"] += 1
            pfn = dag.build_parametric()

            def counted(rng, dyn):
                _STATS["traces"] += 1
                return pfn(rng, dyn)

            jfn = jax.jit(counted)
            get_pool().put(_EXEC_DOM, key, jfn)
        else:
            _STATS["hits"] += 1
            _EXEC_DOM.stats["hits"] += 1
    return lambda rng: jfn(rng, dag.dynamic_params())


def measure(dag: ProxyDAG, execute: bool = False, exec_iters: int = 1,
            host_bytes: float = 0.0) -> Dict[str, float]:
    """The tuner's metric vector for ``dag`` under the compile-once contract.

    ``execute=False``: compositional structural metrics only (no tracing
    once edges are cached).  ``execute=True``: additionally times the
    cached executable to derive the rate metrics (mips / flop_rate /
    mem_bw), still without retracing across dynamic-param steps.
    """
    report = structural_report(dag)
    exec_s = 0.0
    if execute:
        cold = dag.canonical_structure_key() not in _EXEC_CACHE
        fn = executable(dag)
        rng = jax.random.PRNGKey(0)
        if cold:                             # exclude compile from the timing
            jax.block_until_ready(fn(rng))
        t0 = time.perf_counter()
        for _ in range(max(exec_iters, 1)):
            out = fn(rng)
        jax.block_until_ready(out)
        exec_s = (time.perf_counter() - t0) / max(exec_iters, 1)
    return metric_vector(report, host_bytes=host_bytes, exec_time=exec_s)


# ---------------------------------------------------------------------------
# workload fingerprints (measurement -> tuner target)
# ---------------------------------------------------------------------------

#: schema version stamped into every serialized fingerprint
FINGERPRINT_VERSION = 1

#: ordered channel names of the fingerprint vector — the engine's flat
#: basis (:data:`_BASIS_FIELDS`) plus total collective bytes, i.e. exactly
#: the channels :func:`repro.core.metrics.metric_vector` reads
FINGERPRINT_CHANNELS: Tuple[str, ...] = _BASIS_FIELDS + ("collective_bytes",)


@dataclasses.dataclass(frozen=True)
class WorkloadFingerprint:
    """A workload's measured cost signature in the engine's channel basis.

    The lossless intermediate between *measurement* and *tuning*: the 13
    :data:`FINGERPRINT_CHANNELS` floats are precisely the CostReport fields
    :func:`~repro.core.metrics.metric_vector` consumes, so
    ``fp.metrics()`` reproduces the metric dict the measurement would have
    produced bit-for-bit — and any tuner accepting a Table-3 target dict
    accepts a fingerprint unchanged (see
    :func:`repro.core.autotune.coerce_target`).

    Attributes:
        name: human label for the fingerprinted workload.
        channels: the channel values, ordered as
            :data:`FINGERPRINT_CHANNELS`.
        host_bytes: host-side IO bytes observed alongside (feeds the
            ``io_fraction`` metric; 0 when unknown).
        source: provenance tag — ``"fn"`` (HLO cost analysis of a jitted
            callable), ``"dag"`` (compositional model of a ProxyDAG /
            spec), ``"report"`` (a CostReport or WorkloadProfile),
            ``"run"`` (a recorded RunReport), ``"serve"`` (a ServeReport's
            per-structure aggregate), or ``"json"`` (deserialized).
        version: schema version (:data:`FINGERPRINT_VERSION`).
    """

    name: str
    channels: Tuple[float, ...]
    host_bytes: float = 0.0
    source: str = "fn"
    version: int = FINGERPRINT_VERSION

    def __post_init__(self):
        if len(self.channels) != len(FINGERPRINT_CHANNELS):
            raise ValueError(
                f"fingerprint needs {len(FINGERPRINT_CHANNELS)} channels "
                f"({', '.join(FINGERPRINT_CHANNELS)}); got "
                f"{len(self.channels)}")

    def vector(self) -> np.ndarray:
        """The channel values as a float64 array (fresh copy)."""
        return np.asarray(self.channels, dtype=np.float64)

    def channel_dict(self) -> Dict[str, float]:
        """Channel name -> value mapping (insertion-ordered)."""
        return dict(zip(FINGERPRINT_CHANNELS, self.channels))

    def metrics(self) -> Dict[str, float]:
        """The tuner-facing metric dict (instruction mix, arithmetic
        intensity, …) reconstructed from the channels — identical to what
        :func:`measure` would report for the fingerprinted workload."""
        return metric_vector(_vec_to_report(self.vector()),
                             host_bytes=self.host_bytes)

    def to_json(self) -> Dict[str, Any]:
        """Versioned, JSON-serializable dict (round-trips via
        :meth:`from_json`)."""
        return {
            "fingerprint_version": self.version,
            "name": self.name,
            "source": self.source,
            "host_bytes": float(self.host_bytes),
            "channels": {k: float(v) for k, v in
                         zip(FINGERPRINT_CHANNELS, self.channels)},
        }

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "WorkloadFingerprint":
        """Validate + rebuild a fingerprint serialized by :meth:`to_json`.

        Raises :class:`repro.api.spec.SpecError` with a path-precise
        message when the payload doesn't match the schema.
        """
        from ..api.spec import validate_fingerprint_json  # avoid cycle
        validate_fingerprint_json(d)
        return cls(
            name=str(d["name"]),
            channels=tuple(float(d["channels"][k])
                           for k in FINGERPRINT_CHANNELS),
            host_bytes=float(d.get("host_bytes", 0.0)),
            source="json",
            version=int(d["fingerprint_version"]),
        )


def _fingerprint_from_vec(vec: np.ndarray, name: str, host_bytes: float,
                          source: str) -> WorkloadFingerprint:
    return WorkloadFingerprint(
        name=name, channels=tuple(float(x) for x in vec),
        host_bytes=float(host_bytes), source=source)


def fingerprint(obj: Any, *args: Any, name: Optional[str] = None,
                host_bytes: Optional[float] = None) -> WorkloadFingerprint:
    """Fingerprint a workload into the engine's channel basis.

    One entry point for every measurement the repo can produce.  Accepts,
    in dispatch order:

    * a :class:`WorkloadFingerprint` (returned as-is, ``name`` aside);
    * a serialized fingerprint dict (``{"fingerprint_version": ...}``);
    * a recorded ``repro.api.RunReport`` — uses the report's attached DAG
      through the compositional model, scaled by the report's batch width,
      with ``host_bytes`` defaulting to the measured ``io_bytes``;
    * a ``repro.api.ServeReport`` — the request-count-weighted sum of the
      served structures' compositional reports;
    * a ``ProxyDAG`` / ``ProxySpec`` / ``ProxyBenchmark`` — the cached
      compositional cost model (zero compiles warm);
    * a ``CostReport`` or ``repro.core.WorkloadProfile``;
    * any jittable callable plus its (abstract or concrete) example
      ``*args`` — lowered once and HLO-cost-analyzed, exactly like
      :func:`repro.core.profiler.characterize`.

    Returns a versioned :class:`WorkloadFingerprint` whose ``metrics()``
    feed straight into ``repro.api.tune_structure(proxy, target=fp)``.
    """
    if isinstance(obj, WorkloadFingerprint):
        if name is not None and name != obj.name:
            return dataclasses.replace(obj, name=name)
        return obj
    if isinstance(obj, dict) and "fingerprint_version" in obj:
        fp = WorkloadFingerprint.from_json(obj)
        return fp if name is None else dataclasses.replace(fp, name=name)

    # recorded stack run: RunReport carries the executed DAG
    if hasattr(obj, "wall_s") and hasattr(obj, "io_bytes"):
        dag = getattr(obj, "dag", None)
        if dag is None:
            raise ValueError(
                "RunReport has no attached DAG (raw-callable runs are not "
                "fingerprintable from the report; fingerprint the callable "
                "directly: fingerprint(fn, *args))")
        vec = _report_to_vec(structural_report(dag)) * max(
            int(getattr(obj, "batch", 1) or 1), 1)
        hb = float(obj.io_bytes) if host_bytes is None else host_bytes
        return _fingerprint_from_vec(
            vec, name or f"run:{obj.stack}", hb, "run")

    # serve trace: per-structure aggregate weighted by request mix
    if hasattr(obj, "structure_mix") and hasattr(obj, "templates"):
        mix = dict(obj.structure_mix)
        templates = dict(obj.templates or {})
        missing = sorted(set(mix) - set(templates))
        if not mix or missing:
            raise ValueError(
                "ServeReport is missing structure templates for "
                f"{missing or 'all structures'}; re-run serve() to record "
                "them")
        vec = np.zeros(len(FINGERPRINT_CHANNELS), dtype=np.float64)
        for sname, count in sorted(mix.items()):
            vec += float(count) * _report_to_vec(
                structural_report(templates[sname]))
        return _fingerprint_from_vec(
            vec, name or f"serve:{obj.stack}",
            0.0 if host_bytes is None else host_bytes, "serve")

    dag = None
    if isinstance(obj, ProxyDAG):
        dag = obj
    elif hasattr(obj, "to_dag"):                       # ProxySpec
        dag = obj.to_dag()
    elif isinstance(getattr(obj, "dag", None), ProxyDAG):  # ProxyBenchmark
        dag = obj.dag
    if dag is not None:
        return _fingerprint_from_vec(
            _report_to_vec(structural_report(dag)),
            name or getattr(obj, "name", None) or "dag",
            0.0 if host_bytes is None else host_bytes, "dag")

    rep = obj.report if hasattr(obj, "report") else obj  # WorkloadProfile
    if isinstance(rep, CostReport):
        return _fingerprint_from_vec(
            _report_to_vec(rep),
            name or getattr(obj, "name", None) or "report",
            0.0 if host_bytes is None else host_bytes, "report")

    if callable(obj):
        rep = _analyze(obj, args)
        return _fingerprint_from_vec(
            _report_to_vec(rep),
            name or getattr(obj, "__name__", "fn"),
            0.0 if host_bytes is None else host_bytes, "fn")

    raise TypeError(
        f"cannot fingerprint {type(obj).__name__}: expected a callable, "
        "ProxyDAG/ProxySpec/ProxyBenchmark, CostReport/WorkloadProfile, "
        "RunReport, ServeReport, WorkloadFingerprint, or serialized "
        "fingerprint dict")
