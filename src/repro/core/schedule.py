"""ExecutionPlan lowering layer: fused stages + weight-stratified buckets.

The paper's proxies are DAG-like combinations of dwarf components whose
whole point is preserving workload characteristics while shortening
execution 100s of times (§2.1).  The execution layer therefore needs an
explicit, cost-aware plan between a :class:`~repro.core.dag.ProxyDAG` and
the stacks that run it — the same argument Jia et al. and Gao et al. make
for scheduling representative units by *cost* rather than enumerating
them uniformly.  :func:`lower` turns a DAG into an
:class:`ExecutionPlan` exactly once per structure:

* **Fused stages** — adjacent low-cost edges on a private linear chain
  merge into one :class:`FusedStage` executed as a *single*
  ``fori_loop`` whose trip space concatenates every member's weight
  range; a ``lax.switch`` on the segment index applies the owning edge's
  body.  The computation is bit-identical to running each edge's own
  loop in sequence (same bodies, same per-repeat rng folds, same order)
  while the jaxpr carries one ``while`` op per stage instead of one per
  edge, and staged drivers (the hadoop stack) spill per *stage* instead
  of per edge — cutting host-spill volume.  The fusion decision is fed
  by the :mod:`repro.core.engine` compositional cost model (cached
  per-edge body reports) under ``REPRO_FUSION_THRESHOLD``; ``0``
  disables fusion (the legacy one-stage-per-edge path).  A fused stage
  whose members all carry registered Pallas kernel bodies is a
  **MegaStage** (``FusedStage.mega``): when the live dispatch resolves
  every member to the ``"pallas"`` backend it executes as *one*
  :mod:`repro.kernels.megakernel` kernel — grid over the segments,
  carry resident in VMEM scratch, per-segment operand loads pipelined —
  bit-identical to (and demotable per trace to) the switch path.
* **Bucket schedules** — a population of dynamic-param candidates
  executed as one vmapped batched ``while`` runs max-over-candidates
  trips, so one straggler inflates the whole batch (the
  ``exec_speedup_x < 1`` regression in ``BENCH_engine.json``).
  :meth:`ExecutionPlan.bucket_schedule` stratifies candidates by total
  weighted cost into equal-size buckets; each bucket's vmapped ``while``
  then runs its own (much tighter) trip bound, recovering the
  sequential-sum cost model.  Buckets share one compiled executable —
  every bucket has the same size, so the cache key
  ``(plan.structure_key(), bucket_size)`` stays constant across sweeps:
  zero retraces, at most one executable per bucket signature.

The plan cache is keyed on ``(dag.canonical_structure_key(), threshold)``
(stable under isomorphic node relabeling — machine-generated structures
that only rename nodes share plans and executables): fusion
grouping is decided from the weights seen at first lowering and then
*reused* for every dynamic-param setting of the structure (grouping is
correctness-neutral; re-lowering per weight step would break the
compile-once contract).  The *static* :meth:`ExecutionPlan.build` form
bakes lowering-time params in, so callers that need current values baked
(the profiler path) lower fresh with ``cache=False``.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dag import (Edge, ProxyDAG, _accumulate, _edge_out, _gather_inputs,
                  _init_sources, _output)
from .pool import get_pool
from .dwarfs import get_component
from .dwarfs.base import fit_buffer

#: default fusion budget (flops + vpu ops + bytes of one stage, weights
#: included) — sized so that the Table-3 proxies' cheap glue chains fuse
#: (terasort's graph tail ~1.4e8, kmeans' sort/count tail ~1.2e7) while
#: their dominant stages (terasort merge_sort ~1.3e10, pagerank spmv
#: ~1.6e9) stay standalone loops
DEFAULT_FUSION_THRESHOLD = 2.0e8


def fusion_threshold() -> float:
    """Resolve the fusion cost threshold (``REPRO_FUSION_THRESHOLD`` env
    var, empty/unset -> the default; ``0`` disables fusion)."""
    raw = os.environ.get("REPRO_FUSION_THRESHOLD")
    if raw is None or raw.strip() == "":
        return DEFAULT_FUSION_THRESHOLD
    return float(raw)


def population_buckets() -> Optional[int]:
    """Resolve the population bucket *count* override
    (``REPRO_POP_BUCKETS`` env var; ``None`` when unset — the per-device
    bucket-size policy applies; ``1`` disables stratification)."""
    raw = os.environ.get("REPRO_POP_BUCKETS")
    if raw is None or raw.strip() == "":
        return None
    return max(1, int(raw))


def population_workers() -> int:
    """Host threads dispatching population strata concurrently
    (``REPRO_POP_WORKERS`` env var; default ``min(4, cpu_count)``).

    The dwarf bodies (sort, gather, hash) barely engage XLA's intra-op
    pool at proxy sizes, so a sequential candidate sweep leaves cores
    idle; jitted executions release the GIL, making a small thread pool
    over the per-candidate calls the CPU analogue of sharding the
    candidate axis over a mesh.  ``1`` restores serial dispatch."""
    raw = os.environ.get("REPRO_POP_WORKERS")
    if raw is not None and raw.strip() != "":
        return max(1, int(raw))
    return max(1, min(4, os.cpu_count() or 1))


def resolve_bucket_size(n: int) -> int:
    """Default bucket size for an ``n``-candidate population.

    Unless ``REPRO_POP_BUCKETS`` pins a bucket count, each bucket holds
    exactly one candidate lane per device: on a single-device CPU that is
    the *fully* stratified schedule (every candidate trips exactly its own
    weights — the sequential-sum cost model with compiled-call dispatch,
    measured >1.5x over the per-candidate clone/apply/run loop on
    straggler-heavy populations), while on a mesh each bucket fills the
    device axis so the candidate dimension still shards.  CPU vmapped
    ``while`` lanes do not vectorize for the sort/gather-heavy dwarf
    bodies, so wider host buckets only multiply masked work.
    """
    buckets = population_buckets()
    if buckets is not None:
        return max(1, math.ceil(n / buckets))
    return max(1, min(n, jax.device_count()))


# ---------------------------------------------------------------------------
# lowering: edge costs + fusion partition
# ---------------------------------------------------------------------------


def _edge_body_cost(e: Edge) -> float:
    """Scalar per-repeat cost of one edge body (flops + vpu ops + bytes),
    from the engine's cached compositional report; a body the analysis
    prices at zero falls back to a bytes-proportional estimate."""
    from .engine import _body_report
    rep = _body_report(e)
    cost = float(rep.flops + rep.vpu_ops + rep.bytes_accessed)
    if cost > 0.0:
        return cost
    return float(8 * e.params.rounded().data_size)


def _fusable_links(dag: ProxyDAG, edges: Sequence[Edge]) -> List[bool]:
    """``links[i]`` — may edge ``i+1`` join edge ``i``'s stage?  True only
    for a private linear chain: edge ``i+1`` reads exactly edge ``i``'s
    output, nothing else reads or re-writes that intermediate node, it is
    neither a source nor the sink, and both edges share one buffer size
    (the fused loop's carry shape)."""
    produced: Dict[str, int] = {}
    consumers: Dict[str, List[int]] = {}
    for j, e in enumerate(edges):
        produced[e.dst] = produced.get(e.dst, 0) + 1
        for s in e.src:
            consumers.setdefault(s, []).append(j)
    links = []
    for i in range(len(edges) - 1):
        a, b = edges[i], edges[i + 1]
        mid = a.dst
        links.append(
            list(b.src) == [mid]
            and produced.get(mid, 0) == 1
            and consumers.get(mid, []) == [i + 1]
            and mid not in dag.sources
            and mid != dag.sink
            and a.params.data_size == b.params.data_size)
    return links


@dataclasses.dataclass(frozen=True)
class FusedStage:
    """One execution stage: a run of >=1 consecutive DAG edges."""

    members: Tuple[int, ...]       # original edge indices, consecutive
    src: Tuple[str, ...]           # stage inputs (first member's sources)
    dst: str                       # stage output (last member's dst)
    data_size: int                 # carry buffer size of the fused loop
    cost: float                    # Σ weight × body cost at lowering time
    #: megakernel *capability* (a MegaStage): every member is
    #: pallas_capable with a registered bit-identical kernel body and the
    #: carry fits VMEM.  Structure-only — whether a trace actually takes
    #: the one-kernel form is decided per dispatch (see ``_mega_out``)
    mega: bool = False

    @property
    def fused(self) -> bool:
        return len(self.members) > 1


def _mega_eligible(group: Sequence[Edge]) -> bool:
    """May this fused group lower to the one-kernel megakernel?  Every
    member must be ``pallas_capable`` *and* have a registered segment
    body under its params, the members must share one carry layout
    (:func:`~repro.kernels.megakernel.mega_lane`), and the carry must fit
    the VMEM budget.  Pure structure — no env/backend reads — so the flag
    caches with the plan."""
    from ..kernels.megakernel import CARRY_VMEM_BYTES, mega_capable, mega_lane
    if len(group) < 2:
        return False
    if 4 * group[-1].params.rounded().data_size > CARRY_VMEM_BYTES:
        return False
    return (mega_lane([(e.component, e.params) for e in group]) is not None
            and all(get_component(e.component).pallas_capable
                    and mega_capable(e.component, e.params) for e in group))


def _partition(dag: ProxyDAG, edges: Sequence[Edge],
               threshold: float) -> List[FusedStage]:
    links = _fusable_links(dag, edges)
    fuse_any = threshold > 0.0 and any(links)
    costs = [float(e.params.weight) * (_edge_body_cost(e) if fuse_any
                                       else float(8 * e.params.data_size))
             for e in edges]
    groups: List[List[int]] = [[0]] if edges else []
    acc = costs[0] if edges else 0.0
    for i in range(1, len(edges)):
        if fuse_any and links[i - 1] and acc + costs[i] <= threshold:
            groups[-1].append(i)
            acc += costs[i]
        else:
            groups.append([i])
            acc = costs[i]
    return [FusedStage(members=tuple(g),
                       src=tuple(edges[g[0]].src),
                       dst=edges[g[-1]].dst,
                       data_size=edges[g[-1]].params.data_size,
                       cost=sum(costs[i] for i in g),
                       mega=_mega_eligible([edges[i] for i in g]))
            for g in groups]


# ---------------------------------------------------------------------------
# fused-stage execution (must agree exactly with dag._edge_out semantics)
# ---------------------------------------------------------------------------


def _fused_out(members: Sequence[Tuple[int, Edge]], x: jnp.ndarray,
               rng: jax.Array, dyn_stage: Optional[Tuple]) -> jnp.ndarray:
    """Apply a private chain of edges as ONE ``fori_loop``.

    Trip ``t`` belongs to the segment of the edge whose cumulative weight
    range contains it; a ``lax.switch`` applies that edge's single-repeat
    body with the *same* rng fold the unfused per-edge loop would use
    (``10_000 + 131*edge_index + local_repeat``), so the value sequence is
    identical to running each member's own loop back to back — while the
    jaxpr holds a single ``while`` op for the whole chain.
    """
    k = len(members)
    ps, ws = [], []
    for m, (ei, e) in enumerate(members):
        p = e.params
        dyn = dyn_stage[m] if dyn_stage is not None else None
        if dyn:
            extra_dyn = {kk: v for kk, v in dyn.items() if kk != "weight"}
            if extra_dyn:
                p = p.replace(extra={**p.extra, **extra_dyn})
        w = dyn["weight"] if dyn and "weight" in dyn else p.weight
        ps.append(p)
        ws.append(w)
    size = ps[0].data_size
    x0 = fit_buffer(x, size)

    if all(isinstance(w, int) for w in ws):
        # static weights: keep the trip count a Python int so the loop
        # lowers with known_trip_count (exact profiler attribution)
        ends_np = np.cumsum(np.asarray(ws, np.int64))
        total: Any = int(ends_np[-1])
        if total == 0:
            return x0
        ends = jnp.asarray(ends_np, jnp.int32)
        starts = jnp.asarray(ends_np - np.asarray(ws, np.int64), jnp.int32)
    else:
        # unrolled running sum (k is small and static): no scan op in the
        # jaxpr, the fused loop is the only loop this stage contributes
        acc = jnp.asarray(0, jnp.int32)
        starts_l, ends_l = [], []
        for w in ws:
            starts_l.append(acc)
            acc = acc + jnp.asarray(w, jnp.int32)
            ends_l.append(acc)
        ends = jnp.stack(ends_l)
        starts = jnp.stack(starts_l)
        total = acc

    branches = []
    for m, (ei, e) in enumerate(members):
        comp = get_component(e.component)

        def branch(operand, _comp=comp, _p=ps[m], _ei=ei):
            carry, local = operand
            r = jax.random.fold_in(rng, 10_000 + 131 * _ei + local)
            return fit_buffer(_comp(carry, _p, r), size)

        branches.append(branch)

    def body(t, carry):
        # segment of trip t = #cumulative-ends <= t (vectorized compare —
        # no scan/sort op); clip guards the masked tail trips a batched
        # while runs for already-finished lanes
        seg = jnp.clip(jnp.sum((ends <= t).astype(jnp.int32)), 0, k - 1)
        local = t - starts[seg]
        return jax.lax.switch(seg, branches, (carry, local))

    return jax.lax.fori_loop(0, total, body, x0)


#: per-trace megakernel dispatch counters: "mega" — a MegaStage traced
#: through the one-kernel path; "fallback" — a MegaStage demoted to the
#: switch path at trace time (degraded/forced backend, REPRO_MEGAKERNEL
#: off, a traced kernel-static extra).  Non-eligible stages don't count.
MEGA_STATS = {"mega": 0, "fallback": 0}


def mega_stats() -> Dict[str, int]:
    return dict(MEGA_STATS)


def reset_mega_stats() -> None:
    for k in MEGA_STATS:
        MEGA_STATS[k] = 0


def _mega_out(members: Sequence[Tuple[int, Edge]], x: jnp.ndarray,
              rng: jax.Array, dyn_stage: Optional[Tuple]
              ) -> Optional[jnp.ndarray]:
    """One-kernel form of :func:`_fused_out` — same member order, same
    per-member trip counts, bodies value-identical per repeat (and
    rng-free, which registration enforces), so the result is
    bit-identical to the switch path.

    Returns ``None`` when the *live* dispatch resolves away from the
    megakernel — ``REPRO_MEGAKERNEL`` off, any member's backend (env,
    per-edge pin, or the circuit breaker's :func:`forced_backend`
    degrade) resolving to ``"xla"``, a kernel-static extra arriving as a
    traced scalar, or a non-f32 carry — and the caller falls back to
    :func:`_fused_out`.  The decision happens at trace time; every
    executable cache key carries the backend override and the megakernel
    flag, so demoted and promoted traces never share an executable."""
    from ..kernels.dispatch import default_interpret, megakernel_enabled
    from ..kernels.megakernel import mega_body, mega_lane, mega_stage_kernel
    if not megakernel_enabled():
        return None
    ws, bodies = [], []
    for m, (ei, e) in enumerate(members):
        p = e.params.rounded()
        dyn = dyn_stage[m] if dyn_stage is not None else None
        if dyn and any(kk != "weight" for kk in dyn):
            return None          # traced extras can't be kernel statics
        comp = get_component(e.component)
        if not comp.uses_pallas(p):
            return None
        body = mega_body(e.component, p)
        if body is None:
            return None
        ws.append(dyn["weight"] if dyn and "weight" in dyn else p.weight)
        bodies.append(body)
    x0 = fit_buffer(x, members[0][1].params.rounded().data_size)
    if x0.dtype != jnp.float32:
        return None
    weights = jnp.stack([jnp.asarray(w, jnp.int32) for w in ws])
    # eligibility (_mega_eligible) guarantees the members share a layout
    lane = mega_lane([(e.component, e.params) for _, e in members])
    out = mega_stage_kernel(x0, weights, bodies, lane,
                            interpret=default_interpret())
    # The kernel's buffer is bit-identical to the switch path, but XLA may
    # fuse a downstream reduce *into* the interpret-mode lowering with a
    # different accumulation order than it picks against the switch path's
    # opaque while-loop output.  Pin the boundary so consumers see the same
    # opaque producer either way and the whole program stays bit-identical.
    return jax.lax.optimization_barrier(out)


# ---------------------------------------------------------------------------
# bucket schedules (weight-stratified population execution)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One stratum of a candidate population, padded to the shared size."""

    indices: np.ndarray        # candidate positions (trailing entries padded)
    valid: int                 # leading entries that are real candidates
    trip_bound: int            # max total weight (trips) within the bucket
    cost_bound: float          # max stratification cost within the bucket


@dataclasses.dataclass(frozen=True)
class BucketSchedule:
    """Deterministic stratified execution order for one population."""

    buckets: Tuple[Bucket, ...]
    bucket_size: int           # shared size (the executable's batch axis)
    n: int                     # real population size

    @property
    def signature(self) -> Tuple[int, int]:
        """The cache-relevant shape: ``(n_buckets, bucket_size)``."""
        return (len(self.buckets), self.bucket_size)

    def trip_bounds(self) -> List[int]:
        return [b.trip_bound for b in self.buckets]

    def bucket_masses(self) -> np.ndarray:
        """Per-bucket share of the population's total weighted cost —
        where the execution (and tuning-budget) mass actually sits."""
        masses = np.array([b.cost_bound * b.valid for b in self.buckets],
                          dtype=np.float64)
        total = masses.sum()
        return masses / total if total > 0 else masses


def make_bucket_schedule(costs: np.ndarray, trips: np.ndarray,
                         bucket_size: int) -> BucketSchedule:
    """Stratify candidates by ``costs`` into contiguous equal-size buckets
    (stable argsort — deterministic across processes); the last bucket
    pads by repeating its final candidate so every bucket shares one
    executable batch size."""
    costs = np.asarray(costs, np.float64)
    trips = np.asarray(trips, np.float64)
    n = int(costs.shape[0])
    bucket_size = max(1, min(int(bucket_size), n))
    order = np.argsort(costs, kind="stable")
    buckets = []
    for b in range(math.ceil(n / bucket_size)):
        idx = order[b * bucket_size:(b + 1) * bucket_size]
        valid = int(idx.shape[0])
        if valid < bucket_size:
            idx = np.concatenate(
                [idx, np.repeat(idx[-1], bucket_size - valid)])
        buckets.append(Bucket(indices=idx, valid=valid,
                              trip_bound=int(trips[idx].max()),
                              cost_bound=float(costs[idx].max())))
    return BucketSchedule(buckets=tuple(buckets), bucket_size=bucket_size,
                          n=n)


# ---------------------------------------------------------------------------
# ExecutionPlan
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ExecutionPlan:
    """A lowered ProxyDAG: ordered fused stages + population scheduling.

    The plan is the single execution IR every stack consumes — the four
    parallel ``ProxyDAG.build*`` paths lower through here.  ``dyn``
    pytrees keep the per-*edge* layout of ``ProxyDAG.dynamic_params()``
    (stages index into it by member edge), so plan executables are
    drop-in replacements for the legacy parametric fns.
    """

    dag_key: Tuple                 # ProxyDAG.canonical_structure_key() at
                                   # lowering: stable under isomorphic node
                                   # relabeling, so a mutated structure that
                                   # merely renames nodes re-uses every plan
                                   # and downstream stack executable
    sources: Dict[str, int]
    sink: Optional[str]
    edges: List[Edge]              # rounded edge copies (lowering-time params)
    stages: List[FusedStage]
    threshold: float

    # -- identity ------------------------------------------------------------

    def structure_key(self) -> Tuple:
        """Hashable key of the compiled plan: the DAG structure plus the
        stage partition, so a threshold change can never hit an executable
        compiled for a different fusion grouping."""
        return (self.dag_key, self.partition())

    def partition(self) -> Tuple[Tuple[int, ...], ...]:
        return tuple(s.members for s in self.stages)

    @property
    def fused_stage_count(self) -> int:
        return sum(1 for s in self.stages if s.fused)

    @property
    def mega_stage_count(self) -> int:
        return sum(1 for s in self.stages if s.mega)

    def report(self) -> Dict[str, Any]:
        """Lowering diagnostics (the ``plan_sweep`` bench section)."""
        return {
            "edges": len(self.edges),
            "stages": len(self.stages),
            "fused_stages": self.fused_stage_count,
            "mega_stages": self.mega_stage_count,
            "threshold": self.threshold,
            "partition": [list(s.members) for s in self.stages],
            "stage_costs": [s.cost for s in self.stages],
        }

    # -- stage callables -----------------------------------------------------

    def _stage_callable(self, stage: FusedStage) -> Callable:
        """``stage_fn(rng, xs, prev, dyn_stage) -> new dst value`` where
        ``dyn_stage`` is a tuple of the member edges' dyn dicts (or None
        for the baked-in static form).  Single-edge stages execute the
        exact legacy ``_edge_out`` path; fused stages the merged loop."""
        if not stage.fused:
            ei = stage.members[0]
            e = self.edges[ei]

            def single(rng, xs, prev, dyn_stage):
                dyn = dyn_stage[0] if dyn_stage is not None else None
                out = _edge_out(e, ei, _gather_inputs(e, list(xs)), rng,
                                dyn=dyn)
                return _accumulate(prev, out)

            return single

        members = [(ei, self.edges[ei]) for ei in stage.members]
        first = members[0][1]
        mega = stage.mega

        def fused(rng, xs, prev, dyn_stage):
            x = _gather_inputs(first, list(xs))
            out = _mega_out(members, x, rng, dyn_stage) if mega else None
            if out is not None:
                MEGA_STATS["mega"] += 1          # per trace, not per call
            else:
                if mega:
                    MEGA_STATS["fallback"] += 1
                out = _fused_out(members, x, rng, dyn_stage)
            return _accumulate(prev, out)

        return fused

    def _stage_dyn(self, stage: FusedStage, dyn) -> Optional[Tuple]:
        return (None if dyn is None
                else tuple(dyn[ei] for ei in stage.members))

    # -- whole-plan executables ----------------------------------------------

    def build_parametric(self) -> Callable:
        """``fn(rng, dyn) -> scalar`` — ``dyn`` is a
        ``ProxyDAG.dynamic_params()``-shaped pytree of traced scalars (the
        compile-once/run-many form every stack caches)."""
        stage_fns = [self._stage_callable(s) for s in self.stages]
        sources, sink, edges = dict(self.sources), self.sink, self.edges
        stages = self.stages

        def execute(rng: jax.Array, dyn) -> jnp.ndarray:
            nodes = _init_sources(sources, rng)
            for stage, fn in zip(stages, stage_fns):
                xs = [nodes[s] for s in stage.src]
                nodes[stage.dst] = fn(rng, xs, nodes.get(stage.dst),
                                      self._stage_dyn(stage, dyn))
            return _output(nodes, sink, edges)

        return execute

    def build(self) -> Callable[[jax.Array], jnp.ndarray]:
        """Static form: the plan's lowering-time params baked in.  Lower
        with ``cache=False`` when the *current* DAG values must be baked
        (the profiler path) — a cached plan keeps first-lowering params."""
        pfn = self.build_parametric()
        return lambda rng: pfn(rng, None)

    def build_population(self) -> Callable:
        """``fn(rng, dyn_batched) -> (n,)`` — the canonical vmapped
        population form; per-lane computation is the exact
        :meth:`build_parametric` program (bucketed drivers call this once
        per bucket with the bucket's slice)."""
        pfn = self.build_parametric()

        def population(rng: jax.Array, dyn_batched) -> jnp.ndarray:
            return jax.vmap(lambda dyn: pfn(rng, dyn))(dyn_batched)

        return population

    def stages_parametric(self):
        """Staged form at fused-stage granularity (the hadoop execution
        shape: one host spill per *stage*, not per edge).

        Returns ``(init_fn, stages, finalize_fn)`` with ``stages`` a list
        of ``(src_names, dst, stage_fn, stage_key)``;
        ``stage_fn(rng, xs, prev, dyn_stage)`` takes the member edges' dyn
        dicts as a tuple (or ``None``) and ``stage_key`` identifies the
        compiled stage (member indices seed the rng folds, so they are
        part of the identity alongside the structural keys)."""
        sources, sink, edges = dict(self.sources), self.sink, self.edges

        def init_fn(rng: jax.Array) -> Dict[str, jnp.ndarray]:
            return _init_sources(sources, rng)

        stages = [(list(s.src), s.dst, self._stage_callable(s),
                   (s.members, tuple(edges[ei].structure_key()
                                     for ei in s.members)))
                  for s in self.stages]

        def finalize_fn(nodes: Dict[str, jnp.ndarray]) -> jnp.ndarray:
            return _output(nodes, sink, edges)

        return init_fn, stages, finalize_fn

    # -- population scheduling ----------------------------------------------

    def stage_dyn_tuples(self, dyn) -> List[Optional[Tuple]]:
        """Per-stage dyn tuples in stage order (staged-driver plumbing)."""
        return [self._stage_dyn(s, dyn) for s in self.stages]

    def candidate_costs(self, dynb) -> Tuple[np.ndarray, np.ndarray]:
        """Per-candidate ``(weighted_cost, total_trips)`` from a stacked
        dynamic-param pytree — the stratification key.  Cost weights each
        edge's repeat count by its lowering-time body cost so a candidate
        heavy on an expensive edge lands in a later bucket than one heavy
        on glue."""
        sizes = {int(v.shape[0]) for d in dynb for v in d.values()
                 if getattr(v, "shape", ())}
        n = sizes.pop() if len(sizes) == 1 else 1
        costs = np.zeros(n, np.float64)
        trips = np.zeros(n, np.float64)
        for ei, e in enumerate(self.edges):
            d = dynb[ei] if ei < len(dynb) else {}
            w = (np.asarray(d["weight"], np.float64) if "weight" in d
                 else np.full(n, float(e.params.weight)))
            costs += np.round(np.maximum(w, 0.0)) \
                * max(_edge_body_cost(e), 1.0)
            trips += w
        return costs, trips

    def bucket_schedule(self, dynb, bucket_size: Optional[int] = None
                        ) -> BucketSchedule:
        """Weight-stratified :class:`BucketSchedule` for a stacked dyn
        pytree.  ``bucket_size`` defaults to :func:`resolve_bucket_size`
        (one lane per device, ``REPRO_POP_BUCKETS`` override); the
        schedule is a pure function of the candidate values —
        deterministic across processes (stable argsort over float64
        costs)."""
        costs, trips = self.candidate_costs(dynb)
        n = int(costs.shape[0])
        if bucket_size is None:
            bucket_size = resolve_bucket_size(n)
        return make_bucket_schedule(costs, trips, bucket_size)


# ---------------------------------------------------------------------------
# lower() + plan cache
# ---------------------------------------------------------------------------

_PLAN_CACHE: Dict[Tuple, ExecutionPlan] = {}
_PLAN_CACHE_CAP = 512
_PLAN_STATS = {"hits": 0, "misses": 0}

#: the plan cache is a pool domain like every other compiled-artifact
#: cache; lookups mirror into _PLAN_STATS so plan_stats() keeps working
_PLAN_DOM = get_pool().register("plans", _PLAN_CACHE, kind="plan",
                                cap=_PLAN_CACHE_CAP, mirror=_PLAN_STATS)


def plan_stats() -> Dict[str, int]:
    return dict(_PLAN_STATS)


def reset_plan_stats() -> None:
    for k in _PLAN_STATS:
        _PLAN_STATS[k] = 0


def clear_plan_cache() -> None:
    get_pool().clear("plans")


def _lower(dag: ProxyDAG, threshold: float) -> ExecutionPlan:
    dag.validate()
    edges = dag._rounded_edges()
    return ExecutionPlan(dag_key=dag.canonical_structure_key(),
                         sources=dict(dag.sources),
                         sink=dag.sink,
                         edges=edges,
                         stages=_partition(dag, edges, threshold),
                         threshold=threshold)


def lower_population(dag: ProxyDAG) -> ExecutionPlan:
    """Plan for *population* (candidate-batched) execution on the in-memory
    stacks: always unfused.  Under a batched candidate axis a fused
    stage's ``lax.switch`` must execute every branch per trip (vmap
    semantics), and per-edge loops give the bucket schedule exactly the
    per-edge trip bounds it stratifies — stage fusion only multiplies
    masked work there.  The hadoop staged driver still consumes the fused
    :func:`lower` plan for populations: its modeled cost is spill volume,
    which shrinks with the stage count."""
    return lower(dag, threshold=0.0)


def lower(dag: ProxyDAG, threshold: Optional[float] = None,
          cache: bool = True) -> ExecutionPlan:
    """Lower a ProxyDAG into an :class:`ExecutionPlan` — once per
    ``(structure, threshold)``.

    The cached plan is shared by every same-structure DAG regardless of
    its current dynamic params (they enter the parametric executables as
    arguments); pass ``cache=False`` to force a fresh lowering whose
    *static* ``build()`` form bakes the caller's current values.
    """
    thr = fusion_threshold() if threshold is None else float(threshold)
    if not cache:
        return _lower(dag, thr)
    key = (dag.canonical_structure_key(), thr)
    return get_pool().get(_PLAN_DOM, key, lambda: _lower(dag, thr))
