"""Software-stack execution protocol (paper §2.2.2, unified API).

The paper implements every dwarf component on OpenMP / MPI / Hadoop / Spark
because "software stack has great influences on workload behaviors".  The
seed exposed four ad-hoc functions with different signatures; this module
redesigns that axis around one contract:

    stack = get_stack("hadoop")
    report = stack.run(executable, *args)     # -> RunReport

where ``executable`` may be a raw jit-able function, a ``ProxyDAG``, a
``ProxyBenchmark``, a ``ProxySpec``, or a ``Workload`` — the stack coerces
it and reports result, wall time and host<->device traffic uniformly.
``run_batch`` vmaps rng-driven executables over a batch of keys for
high-throughput proxy serving.

JAX-native execution models:

  * ``openmp``  — single-process jit; XLA intra-op threading = OpenMP threads.
  * ``mpi``     — explicit SPMD via shard_map over a device mesh with the
                  collectives spelled out (the MPI execution model).
  * ``spark``   — inputs placed on the mesh with sharding constraints;
                  intermediates stay device-resident ("in-memory RDD").
                  Plan programs run per device under shard_map, as
                  ``mpi``'s do: XLA cannot partition a Pallas kernel.
  * ``hadoop``  — staged execution: every intermediate DAG node is
                  materialized through *host* memory ("HDFS spill"), which is
                  the disk-I/O behaviour the paper measures for Hadoop jobs.
"""

from __future__ import annotations

import abc
import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import schedule as plans
from ..core.cachetools import hit_rate
from ..core.dag import ProxyDAG
from ..core.pool import get_pool
from ..kernels.dispatch import backend_override, megakernel_enabled


# ---------------------------------------------------------------------------
# Compiled-executable cache (compile-once/run-many)
# ---------------------------------------------------------------------------
#
# DAG executables lower through ``repro.core.schedule.lower`` into an
# ExecutionPlan and compile from the plan's *parametric* form: weights and
# shape-free extras enter as jitted arguments, so one executable serves
# every dynamic-param setting of a structure.  Each stack keeps its own
# cache (its execution model is part of the compiled program) keyed on
# ``ExecutionPlan.structure_key()`` — the DAG structure *plus* the fusion
# partition, so a ``REPRO_FUSION_THRESHOLD`` change never hits an
# executable compiled for another grouping; population executables add the
# bucket size (``(plan.structure_key(), bucket_size)``).  These
# module-level counters expose hit/miss/trace activity for the no-retrace
# tests and the engine benchmarks.

CACHE_STATS = {"hits": 0, "misses": 0, "traces": 0, "evictions": 0}

#: executables retained per stack (FIFO eviction; a long-lived tuning or
#: serving process sweeping *structural* params must not accumulate
#: compiled programs without bound).  A structural search proposes many
#: distinct structures, so the cap is tunable (``REPRO_EXEC_CACHE_CAP``)
#: and the ``evictions`` counter exposes thrash: evictions growing while
#: the same structures keep re-running means the cap is too tight and
#: every revisit re-compiles.
CACHE_CAP = 256


def cache_cap() -> int:
    """Resolve the per-stack executable-cache cap
    (``REPRO_EXEC_CACHE_CAP`` env var; default :data:`CACHE_CAP`)."""
    import os
    raw = os.environ.get("REPRO_EXEC_CACHE_CAP")
    if raw is None or raw.strip() == "":
        return CACHE_CAP
    return max(1, int(raw))


def cache_stats() -> Dict[str, float]:
    """Aggregate executable-cache counters across every stack instance
    (mirrored from the per-instance pool domains), plus the warm-serving
    ``hit_rate`` the serving bench reports; per-domain breakdowns live in
    ``repro.core.pool.get_pool().stats()``."""
    stats: Dict[str, float] = dict(CACHE_STATS)
    stats["hit_rate"] = hit_rate(stats)
    return stats


def reset_cache_stats() -> None:
    """Zero the process-wide executable-cache counters."""
    for k in CACHE_STATS:
        CACHE_STATS[k] = 0


def _donate_argnums() -> Tuple[int, ...]:
    # donate the dynamic-param buffers (rebuilt fresh per call); CPU has no
    # donation support, so skip it there to avoid per-compile warnings
    return () if jax.default_backend() == "cpu" else (1,)


# ---------------------------------------------------------------------------
# Failure classification (the serving engine's retry policy input)
# ---------------------------------------------------------------------------

#: classes ``classify_failure`` can return; everything but "fatal" is
#: retryable (a re-dispatch can plausibly succeed)
FAILURE_CLASSES = ("injected", "resource", "fatal", "transient")


def classify_failure(exc: BaseException) -> str:
    """Classify an executable-dispatch exception for the retry policy.

    * ``"injected"`` — a :class:`repro.faults.InjectedFailure` (chaos
      testing); retryable by construction.
    * ``"resource"``  — allocation / OOM-shaped runtime errors; retryable
      after degradation (smaller chunks, evicted executables).
    * ``"fatal"``     — caller bugs (bad types/shapes/keys); retrying the
      identical dispatch cannot succeed, fail the request terminally.
    * ``"transient"`` — everything else (backend hiccups); retryable.
    """
    from ..faults import InjectedFailure
    if isinstance(exc, InjectedFailure):
        return "injected"
    msg = str(exc).upper()
    if ("RESOURCE_EXHAUSTED" in msg or "OUT OF MEMORY" in msg
            or "OOM" in msg or isinstance(exc, MemoryError)):
        return "resource"
    if isinstance(exc, (TypeError, ValueError, KeyError, IndexError,
                        AttributeError)):
        return "fatal"
    return "transient"


def failure_is_retryable(exc: BaseException) -> bool:
    """True when ``classify_failure`` deems the exception transient —
    the serving engine's retry/bisection policies key off this."""
    return classify_failure(exc) != "fatal"


# ---------------------------------------------------------------------------
# RunReport
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunReport:
    """Uniform result of ``Stack.run`` across every software stack."""

    stack: str                   # registry name of the executing stack
    wall_s: float                # end-to-end wall time (incl. compile)
    #: modelled host traffic ("disk I/O" analog): each spilled buffer
    #: counts twice (write + read back), sources once
    io_bytes: float
    result: Any = None           # the executable's output pytree
    batch: int = 1               # number of rng instances executed
    result_bytes: float = 0.0    # size of the output pytree
    #: bytes the stack itself copied host-to-device (``jnp.asarray`` of a
    #: host array) and device-to-host (``np.asarray`` of a device array)
    h2d_bytes: float = 0.0
    d2h_bytes: float = 0.0
    #: the executed ProxyDAG when the run came from a DAG-bearing
    #: executable (None for raw callables) — lets
    #: ``repro.api.fingerprint(report)`` recover the measured channel
    #: vector without re-running anything
    dag: Any = dataclasses.field(default=None, repr=False)

    @property
    def throughput(self) -> float:
        """Executions per second (batched proxy serving metric)."""
        return self.batch / max(self.wall_s, 1e-12)

    @property
    def io_bandwidth(self) -> float:
        """Host-traffic bandwidth in bytes/s (paper Fig. 7 analog)."""
        return self.io_bytes / max(self.wall_s, 1e-12)

    def to_json(self) -> Dict[str, float]:
        return {"stack": self.stack, "wall_s": self.wall_s,
                "io_bytes": self.io_bytes, "batch": self.batch,
                "result_bytes": self.result_bytes,
                "h2d_bytes": self.h2d_bytes, "d2h_bytes": self.d2h_bytes,
                "throughput": self.throughput}


class _Traffic:
    """Host traffic of one run: the modelled spill (``io``) and the bytes
    the stack copied itself, by direction.  Copies go through :meth:`up`
    and :meth:`down`, which count them; the population path uploads on
    worker threads, hence the lock."""

    def __init__(self):
        self.io = 0.0
        self.h2d = 0.0
        self.d2h = 0.0
        self._lock = threading.Lock()

    def up(self, x, dtype=None) -> jax.Array:
        """``jnp.asarray(x)``, counting a host array's bytes."""
        if not isinstance(x, jax.Array):
            x = np.asarray(x, dtype)
            with self._lock:
                self.h2d += x.nbytes
        return jnp.asarray(x, dtype)

    def down(self, x) -> np.ndarray:
        """``np.asarray(x)``, counting a device array's bytes."""
        host = np.asarray(x)
        if isinstance(x, jax.Array):
            with self._lock:
                self.d2h += host.nbytes
        return host

    def report(self, stack: str, wall_s: float, result: Any, batch: int,
               dag: Any = None) -> "RunReport":
        return RunReport(stack=stack, wall_s=wall_s, io_bytes=self.io,
                         result=result, batch=batch,
                         result_bytes=_tree_bytes(result),
                         h2d_bytes=self.h2d, d2h_bytes=self.d2h, dag=dag)


#: a program span: a host annotation in the profiler's trace, on the
#: device trace's clock (a cheap no-op while no profiler runs)
_span = jax.profiler.TraceAnnotation


def _tree_bytes(out: Any) -> float:
    # jax/np arrays expose .nbytes without a device-to-host transfer;
    # only Python scalars need materializing
    total = 0.0
    for x in jax.tree_util.tree_leaves(out):
        nbytes = getattr(x, "nbytes", None)
        total += float(np.asarray(x).nbytes if nbytes is None else nbytes)
    return total


# ---------------------------------------------------------------------------
# Executable coercion
# ---------------------------------------------------------------------------


def _extract_dag(executable: Any) -> Optional[ProxyDAG]:
    if isinstance(executable, ProxyDAG):
        return executable
    dag = getattr(executable, "dag", None)          # ProxyBenchmark
    if isinstance(dag, ProxyDAG):
        return dag
    if hasattr(executable, "to_dag"):               # ProxySpec
        return executable.to_dag()
    return None


def _as_fn(executable: Any, args: Tuple) -> Tuple[Callable, Tuple]:
    """Coerce (executable, args) -> (jit-able fn, concrete args)."""
    if callable(executable) and not hasattr(executable, "make_inputs"):
        return executable, args
    if hasattr(executable, "make_inputs"):          # core.workloads.Workload
        from ..core.workloads import workload_step_fn
        scale = args[0] if args else "tiny"
        return workload_step_fn(executable.name, scale)
    raise TypeError(f"cannot execute object of type "
                    f"{type(executable).__name__} on a Stack; expected a "
                    f"callable, ProxyDAG, ProxyBenchmark, ProxySpec, or "
                    f"Workload")


def _default_rng(rng: Optional[jax.Array]) -> jax.Array:
    return jax.random.PRNGKey(0) if rng is None else rng


def _take_candidates(dynb: Tuple, indices, io: _Traffic) -> Tuple:
    """Gather one bucket's slice of a stacked dyn pytree (leading
    candidate axis) — shapes depend only on the bucket size, so every
    same-size bucket reuses one compiled executable."""
    sel = io.up(indices, np.int32)
    return jax.tree_util.tree_map(lambda v: v[sel], dynb)


# ---------------------------------------------------------------------------
# Stack protocol
# ---------------------------------------------------------------------------


class Stack(abc.ABC):
    """One software-stack execution model.

    Subclasses implement ``_execute(fn, args, io) -> result`` for
    raw-fn/workload executables, counting the host copies they make in
    ``io`` (a :class:`_Traffic`); coercion, timing, batching and
    reporting are shared.  DAG executables take the compile-once fast
    path instead: they lower to an ``ExecutionPlan``
    (``repro.core.schedule.lower`` —
    fused stages under the live ``REPRO_FUSION_THRESHOLD``) and
    ``run``/``run_batch`` fetch a cached parametric executable via
    ``_compiled_plan``, so a stack that needs its execution model applied
    to DAG runs overrides ``_wrap_parametric`` (bake the model into the
    compiled fn — see ``MPIStack``) and/or ``_dag_run``/``_dag_run_batch``
    (placement and io accounting — see ``SparkStack``/``HadoopStack``).
    ``run_population`` executes a plan's weight-stratified
    ``BucketSchedule``: one vmapped call per bucket, every bucket sharing
    the one ``(plan, bucket_size)`` executable."""

    name: str = "abstract"

    @abc.abstractmethod
    def _execute(self, fn: Callable, args: Tuple, io: _Traffic) -> Any:
        """Run ``fn(*args)`` under this execution model; host traffic
        goes to ``io``."""

    # -- compiled plan executables ------------------------------------------

    def exec_domain(self):
        """This instance's compiled-executable domain in the process-wide
        :class:`~repro.core.pool.ExecutablePool`.  Registered lazily and
        per *instance* (a fresh stack starts cold — the compile-accounting
        tests and benchmarks rely on that), auto-unregistered when the
        instance dies; lookups mirror into the module-level
        :data:`CACHE_STATS` so the aggregate counters keep working."""
        dom = self.__dict__.get("_pool_domain")
        if dom is None:
            dom = get_pool().register_instance(
                self, f"stack:{self.name}", kind="executable",
                mirror=CACHE_STATS)
            self.__dict__["_pool_domain"] = dom
            self.__dict__["_dag_cache"] = dom.cache
        dom.cap = cache_cap()    # live env resolution, as cached_get did
        return dom

    def _exec_key(self, *parts) -> Tuple:
        """Executable cache key: the caller's parts plus the live
        degradation backend override (:func:`repro.kernels.dispatch.
        backend_override`) — ``None`` in normal operation, so warm keys
        are unchanged; a degraded dispatch with XLA forced must compile
        (and cache) its own executable rather than be handed one traced
        with the failing backend — and the live megakernel arming flag
        (:func:`repro.kernels.dispatch.megakernel_enabled`), since a
        MegaStage traces a different program per flag setting."""
        return (*parts, backend_override(), megakernel_enabled())

    @staticmethod
    def _plan_cost(plan) -> float:
        """Recompile cost of one plan's executable under the lowering cost
        model — what the pool's ``"cost"`` eviction policy minimizes
        keeping (:func:`repro.core.pool.pool_policy`)."""
        return float(sum(s.cost for s in plan.stages))

    def _compiled_plan(self, plan, batch: bool) -> Callable:
        """Cached jitted ``fn(rng, dyn)`` for this stack's execution model.
        One compile per (stack, plan structure key, batch-ness); every
        dynamic-param setting of the structure reuses it."""
        return get_pool().get(
            self.exec_domain(), self._exec_key(batch, plan.structure_key()),
            lambda: self._wrap_parametric(plan.build_parametric(), batch),
            cost=self._plan_cost(plan))

    def _wrap_parametric(self, pfn: Callable, batch: bool) -> Callable:
        """Bake this stack's execution model into a jitted parametric fn."""
        if batch:
            def f(rngs, dyn):
                CACHE_STATS["traces"] += 1
                return jax.vmap(lambda r: pfn(r, dyn))(rngs)
        else:
            def f(rng, dyn):
                CACHE_STATS["traces"] += 1
                return pfn(rng, dyn)
        return jax.jit(f, donate_argnums=_donate_argnums())

    def _dag_run(self, dag: ProxyDAG, rng: jax.Array, io: _Traffic) -> Any:
        plan = plans.lower(dag)
        out = self._compiled_plan(plan, batch=False)(rng,
                                                     dag.dynamic_params())
        jax.block_until_ready(out)
        return out

    def _dag_run_batch(self, dag: ProxyDAG, rngs: jax.Array,
                       io: _Traffic) -> Any:
        plan = plans.lower(dag)
        out = self._compiled_plan(plan, batch=True)(rngs,
                                                    dag.dynamic_params())
        jax.block_until_ready(out)
        return out

    # -- population evaluation (one compiled call per weight bucket) ---------

    def _compiled_plan_population(self, plan, n: int) -> Callable:
        """Cached jitted ``fn(rng, dyn_batched)`` evaluating ``n``
        dynamic-param candidates of one plan in a single vmapped call.
        Keyed on ``(plan structure key, bucket size)``: every same-size
        bucket of every sweep reuses it — at most one executable per
        bucket signature, zero retraces per candidate."""
        return get_pool().get(
            self.exec_domain(),
            self._exec_key(("population", n), plan.structure_key()),
            lambda: self._wrap_population(plan, n),
            cost=n * self._plan_cost(plan))

    # -- serving micro-batches (one compiled call per request chunk) ---------

    def _compiled_plan_serve(self, plan, n: int) -> Callable:
        """Cached jitted ``fn(rngs, dynb)`` executing ``n`` heterogeneous
        *requests* of one structure in a single vmapped call.  Unlike the
        population form (one shared rng, candidate-batched dyn), every
        request carries its own rng — the serving micro-batch axis.  Keyed
        on ``(("serve", n), plan.structure_key())``: every same-size
        micro-batch of every stream reuses one executable, so steady-state
        serving compiles at most once per (structure, chunk size)."""
        return get_pool().get(
            self.exec_domain(),
            self._exec_key(("serve", n), plan.structure_key()),
            lambda: self._wrap_serve(plan, n),
            cost=n * self._plan_cost(plan))

    def _wrap_serve(self, plan, n: int) -> Callable:
        """Bake this stack's execution model into the request-batched
        serving form: vmap over *paired* (rng, dyn) request axes.  No
        buffer donation — the serving engine may replay a trace."""
        pfn = plan.build_parametric()

        def f(rngs, dynb):
            CACHE_STATS["traces"] += 1
            return jax.vmap(pfn)(rngs, dynb)

        return jax.jit(f)

    def _serve_call(self, fn: Callable, rngs: jax.Array,
                    dynb: Tuple) -> Any:
        """One serving micro-batch call (placement hook — see SparkStack).
        Not synced: the serving loop's latency accounting blocks."""
        return fn(rngs, dynb)

    def _wrap_population(self, plan, n: int) -> Callable:
        """Bake this stack's execution model into the canonical vmapped
        population form (``ExecutionPlan.build_population``).  No buffer
        donation: callers may reuse a stacked dyn pytree across calls."""
        pop = plan.build_population()

        def f(rng, dynb):
            CACHE_STATS["traces"] += 1
            return pop(rng, dynb)

        return jax.jit(f)

    def _population_call(self, fn: Callable, rng: jax.Array,
                         dynb: Tuple) -> Any:
        """One bucket's executable call (placement hook — see SparkStack).
        Deliberately *not* synced: the bucket loop dispatches every
        stratum and lets the assembly's host transfer force completion,
        overlapping per-bucket Python overhead with device compute."""
        return fn(rng, dynb)

    def _dag_run_population(self, dag: ProxyDAG, rng: jax.Array,
                            dynb: Tuple, n: int, io: _Traffic,
                            bucket_size: Optional[int] = None) -> Any:
        """Bucketed population execution: candidates stratified by total
        weighted cost run one vmapped call per bucket, so each bucket's
        batched ``while`` trips only to its own maximum instead of the
        population-wide straggler — recovering the sequential-sum cost
        model while keeping per-lane results bit-identical to single runs
        (on a TPU, matrix dwarfs inside a vmap of several lanes are the
        exception: they round differently).  Population plans lower
        unfused (``plans.lower_population``): per-edge loops give the
        schedule its per-edge trip bounds, and a fused switch under a
        batched candidate axis would execute every branch per trip."""
        with _span("stack.schedule"):
            plan = plans.lower_population(dag)
            sched = plan.bucket_schedule(dynb, bucket_size)
            single = sched.bucket_size == 1
            if single:
                # fully stratified schedule (the single-device default):
                # every candidate runs exactly its own trips through an
                # *unbatched* parametric executable (no batched-while
                # masking overhead), strata dispatched over a small host
                # thread pool — the CPU analogue of sharding the
                # candidate axis over a mesh
                fn = self._compiled_plan(plan, batch=False)
                host_dynb = jax.tree_util.tree_map(io.down, dynb)
            else:
                fn = self._compiled_plan_population(plan, sched.bucket_size)

        def dispatch(k: int):
            b = sched.buckets[k]
            with _span("stack.dispatch", bucket=k):
                if single:
                    i = int(b.indices[0])
                    dyn = jax.tree_util.tree_map(lambda v: io.up(v[i]),
                                                 host_dynb)
                else:
                    dyn = _take_candidates(dynb, b.indices, io)
                return b, self._population_call(fn, rng, dyn)

        order = range(len(sched.buckets))
        workers = plans.population_workers()
        if (single and workers > 1 and len(order) > 1 and
                type(self)._population_call is Stack._population_call):
            from concurrent.futures import ThreadPoolExecutor
            with ThreadPoolExecutor(max_workers=workers) as pool:
                results = list(pool.map(dispatch, order))
        else:
            results = [dispatch(k) for k in order]
        out_np = None
        with _span("stack.gather"):
            for b, res in results:             # host transfer = the sync
                host = io.down(res)
                if single:
                    host = host[None]
                if out_np is None:
                    out_np = np.empty((sched.n,) + host.shape[1:],
                                      host.dtype)
                out_np[b.indices[:b.valid]] = host[:b.valid]
        with _span("stack.assemble"):
            return io.up(out_np)

    def _coerce_population(self, dag: ProxyDAG, candidates: Any,
                           space: Any) -> Tuple[Tuple, int]:
        """Coerce a ``(n, len(space))`` candidate matrix (or an already
        stacked dyn pytree) into the batched dyn pytree + its size."""
        if getattr(candidates, "ndim", None) == 2:
            if space is None:
                from .params import ParamSpace
                space = ParamSpace.from_dag(dag)
            dynb = space.stack_candidates(dag, candidates)
        else:
            dynb = candidates
        sizes = {int(v.shape[0]) if len(v.shape) else None
                 for d in dynb for v in d.values()}
        if len(sizes) != 1 or None in sizes:
            raise ValueError(
                f"cannot infer the population size from candidate-axis "
                f"sizes {sorted(sizes, key=str)}: pass a (n, len(space)) "
                f"matrix or a pytree stacked by ParamSpace.stack_candidates "
                f"(an unbatched dynamic_params() pytree, or a DAG without "
                f"dynamic params, has no population axis)")
        return dynb, sizes.pop()

    # -- public API ----------------------------------------------------------

    def run(self, executable: Any, *args,
            rng: Optional[jax.Array] = None) -> RunReport:
        """Execute anything on this stack and report uniformly."""
        dag = _extract_dag(executable)
        io = _Traffic()
        t0 = time.perf_counter()
        if dag is not None:
            if args:
                raise TypeError(
                    f"{type(executable).__name__} executables take no "
                    f"positional args; pass the PRNG key as rng=...")
            result = self._dag_run(dag, _default_rng(rng), io)
        else:
            fn, fargs = _as_fn(executable, args)
            if rng is not None:
                if hasattr(executable, "make_inputs"):
                    raise TypeError("Workload executables generate their own "
                                    "inputs; rng= only applies to DAG or "
                                    "rng-driven fn executables")
                fargs = (*fargs, rng)    # fn(*args, rng) convention
            result = self._execute(fn, fargs, io)
        wall = time.perf_counter() - t0
        return io.report(self.name, wall, result, 1, dag)

    def run_batch(self, executable: Any,
                  rngs: jax.Array) -> RunReport:
        """Vectorized execution of an rng-driven executable over a batch of
        PRNG keys (high-throughput proxy serving)."""
        dag = _extract_dag(executable)
        if dag is None and not callable(executable):
            raise TypeError("run_batch needs an rng-driven executable "
                            "(ProxyDAG/ProxyBenchmark/ProxySpec or fn(rng))")
        batch = int(rngs.shape[0])
        io = _Traffic()
        t0 = time.perf_counter()
        if dag is not None:
            result = self._dag_run_batch(dag, rngs, io)
        else:
            result = self._execute_batch(executable, rngs, io)
        wall = time.perf_counter() - t0
        return io.report(self.name, wall, result, batch, dag)

    def run_population(self, executable: Any, candidates: Any, *,
                       rng: Optional[jax.Array] = None,
                       space: Any = None,
                       bucket_size: Optional[int] = None) -> RunReport:
        """Evaluate a *population* of dynamic-param candidates of one DAG
        structure through its weight-stratified bucket schedule (the
        batched-autotuning axis).

        ``candidates`` is either a ``(n, len(space))`` matrix from
        ``ParamSpace.sample``/``sample_dynamic`` (``space`` optional — built
        from the DAG when omitted) or an already-stacked dyn pytree from
        ``ParamSpace.stack_candidates``.  All candidates share the rng;
        the plan's ``BucketSchedule`` strata (``bucket_size`` — default
        ``ceil(n / REPRO_POP_BUCKETS)``) each execute as one vmapped call
        of a single shared executable — one compile per (plan, bucket
        size), zero retraces per candidate — and the candidate axis shards
        over the stack's device mesh where the execution model has one.
        ``result`` holds the per-candidate output stacked on axis 0 in the
        caller's candidate order.
        """
        dag = _extract_dag(executable)
        if dag is None:
            raise TypeError(
                f"run_population needs a DAG executable (ProxyDAG / "
                f"ProxyBenchmark / ProxySpec), got "
                f"{type(executable).__name__}")
        dynb, n = self._coerce_population(dag, candidates, space)
        io = _Traffic()
        t0 = time.perf_counter()
        result = self._dag_run_population(
            dag, _default_rng(rng), dynb, n, io, bucket_size=bucket_size)
        wall = time.perf_counter() - t0
        return io.report(self.name, wall, result, n, dag)

    def _execute_batch(self, fn: Callable, rngs: jax.Array,
                       io: _Traffic) -> Any:
        return self._execute(jax.vmap(fn), (rngs,), io)

    def __repr__(self) -> str:
        return f"<Stack:{self.name}>"


# ---------------------------------------------------------------------------
# Implementations
# ---------------------------------------------------------------------------


def _default_mesh(axis: str) -> Mesh:
    return Mesh(np.array(jax.devices()), (axis,))


class OpenMPStack(Stack):
    """Single-process jit: XLA intra-op threads are the OpenMP threads."""

    name = "openmp"

    def _execute(self, fn, args, io):
        out = jax.jit(fn)(*args)
        jax.block_until_ready(out)
        return out


class _MeshStack(Stack):
    """A stack whose plan programs run on every device of a 1-D mesh.

    Each program runs per device under ``shard_map``: a Pallas kernel
    cannot be partitioned by XLA, so a program that may hold one is never
    handed to the partitioner.  Batch axes — ``run_batch`` rngs,
    population buckets, serving chunks — shard over the mesh axis when
    their size divides it, each device vmapping its own slice; otherwise
    (and for single runs) every device computes the whole program."""

    #: the mesh axis name when none is given
    axis_name = "rank"

    def __init__(self, mesh: Optional[Mesh] = None,
                 axis: Optional[str] = None):
        self.axis = axis or self.axis_name
        self._mesh = mesh          # built lazily: importing repro.api must
                                   # not initialize the JAX backend

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self._mesh = _default_mesh(self.axis)
        return self._mesh

    def _lanes(self, n: int) -> P:
        """Spec of an ``n``-long batch axis: sharded when ``n`` divides
        the mesh axis, else replicated."""
        from ..distributed.sharding import candidate_spec_axis
        ax = candidate_spec_axis(self.mesh, n, prefer=(self.axis,))
        return P() if ax is None else P(ax)

    def _per_device(self, fn: Callable, in_specs, out_specs) -> Callable:
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _single(self, out):
        """Combine a single run's per-device results (identity here)."""
        return out

    def _wrap_parametric(self, pfn, batch):
        if batch:
            def f(rngs, dyn):
                CACHE_STATS["traces"] += 1
                lanes = self._lanes(rngs.shape[0])
                return self._per_device(
                    lambda rs, d: jax.vmap(lambda r: pfn(r, d))(rs),
                    (lanes, P()), lanes)(rngs, dyn)
        else:
            def f(rng, dyn):
                CACHE_STATS["traces"] += 1
                return self._per_device(
                    lambda r, d: self._single(pfn(r, d)),
                    (P(), P()), P())(rng, dyn)
        return jax.jit(f, donate_argnums=_donate_argnums())

    def _wrap_population(self, plan, n):
        """Shard each bucket's candidate axis over the mesh: every device
        vmaps its own slice of the bucket (SPMD tuner sweep)."""
        pop, lanes = plan.build_population(), self._lanes(n)

        def f(rng, dynb):
            CACHE_STATS["traces"] += 1
            return self._per_device(pop, (P(), lanes), lanes)(rng, dynb)

        return jax.jit(f)

    def _wrap_serve(self, plan, n):
        """Shard the serving micro-batch over the mesh: request rngs and
        dyn params shard together on the request axis, each device
        vmapping its own slice of the chunk."""
        pfn, lanes = plan.build_parametric(), self._lanes(n)

        def f(rngs, dynb):
            CACHE_STATS["traces"] += 1
            return self._per_device(jax.vmap(pfn), (lanes, lanes),
                                    lanes)(rngs, dynb)

        return jax.jit(f)


class MPIStack(_MeshStack):
    """Explicit SPMD over a device mesh with collectives spelled out.

    Single runs are replicated across ranks and combined with an
    all-reduce mean (identical per-rank inputs keep results bit-stable
    across any rank count); batched runs shard the rng batch over ranks.
    """

    name = "mpi"

    def _pmean_floats(self, out):
        return jax.tree_util.tree_map(
            lambda x: (jax.lax.pmean(x, self.axis)
                       if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                       else x), out)

    _single = _pmean_floats

    def _execute(self, fn, args, io):
        spmd = self._per_device(lambda *a: self._pmean_floats(fn(*a)),
                                P(), P())
        out = jax.jit(spmd)(*args)
        jax.block_until_ready(out)
        return out

    def _execute_batch(self, fn, rngs, io):
        n = self.mesh.devices.size
        batch = int(rngs.shape[0])
        if batch % n != 0:  # pragma: no cover
            return self._execute(jax.vmap(fn), (rngs,), io)
        spmd = self._per_device(jax.vmap(fn), P(self.axis), P(self.axis))
        out = jax.jit(spmd)(rngs)
        jax.block_until_ready(out)
        return out


class SparkStack(_MeshStack):
    """Global-view placement: inputs are put on the mesh with sharding
    constraints and intermediates stay device-resident (the "in-memory
    RDD" model); the plan programs run per device (:class:`_MeshStack`)."""

    name = "spark"
    axis_name = "worker"

    def _spec_for(self, a: Any) -> P:
        shape = getattr(a, "shape", ())
        n = self.mesh.devices.size
        if len(shape) >= 1 and shape[0] > 0 and shape[0] % n == 0:
            return P(self.axis)
        return P()

    def _execute(self, fn, args, io):
        with self.mesh:
            placed = tuple(
                jax.device_put(a, NamedSharding(self.mesh, self._spec_for(a)))
                if hasattr(a, "shape") else a
                for a in args)
            out = jax.jit(fn)(*placed)
            jax.block_until_ready(out)
        return out

    def _dag_run(self, dag, rng, io):
        fn = self._compiled_plan(plans.lower(dag), batch=False)
        with self.mesh:
            rng = jax.device_put(rng, NamedSharding(self.mesh, P()))
            out = fn(rng, dag.dynamic_params())
            jax.block_until_ready(out)
        return out

    def _dag_run_batch(self, dag, rngs, io):
        fn = self._compiled_plan(plans.lower(dag), batch=True)
        with self.mesh:
            # shard the rng batch over the workers (the "RDD partitions")
            rngs = jax.device_put(
                rngs, NamedSharding(self.mesh, self._spec_for(rngs)))
            out = fn(rngs, dag.dynamic_params())
            jax.block_until_ready(out)
        return out

    def _population_call(self, fn, rng, dynb):
        from ..distributed.sharding import bucket_shardings
        with self.mesh:
            # place each bucket over the workers: every worker evaluates
            # its partition of the bucket's candidate slice (no sync —
            # the assembly's host transfer forces completion)
            dynb = jax.device_put(
                dynb, bucket_shardings(self.mesh, dynb,
                                       prefer=(self.axis,)))
            rng = jax.device_put(rng, NamedSharding(self.mesh, P()))
            return fn(rng, dynb)

    def _serve_call(self, fn, rngs, dynb):
        from ..distributed.sharding import serve_shardings
        with self.mesh:
            # place the micro-batch over the workers: the paired request
            # axes of rngs and dyn params partition together
            rng_s, dyn_s = serve_shardings(self.mesh, rngs, dynb,
                                           prefer=(self.axis,))
            out = fn(jax.device_put(rngs, rng_s),
                     jax.device_put(dynb, dyn_s))
        return out


class HadoopStack(Stack):
    """Staged map -> host-materialized intermediate ("HDFS spill") ->
    reduce.  DAG executables run edge-by-edge with every intermediate node
    round-tripped through host memory; ``io_bytes`` models both directions
    (the paper's disk-I/O bandwidth analog) and ``h2d_bytes`` /
    ``d2h_bytes`` count the copies made."""

    name = "hadoop"

    def __init__(self, n_chunks: int = 8):
        self.n_chunks = n_chunks

    def _execute(self, fn, args, io):
        # opaque fn: run, then spill the result through host memory
        out = jax.jit(fn)(*args)
        jax.block_until_ready(out)
        hosts = jax.tree_util.tree_map(io.down, out)
        io.io += _tree_bytes(hosts) * 2.0            # write + read back
        return jax.tree_util.tree_map(io.up, hosts)

    def _dag_run(self, dag, rng, io):
        return self._run_stages(dag, rng, False, io)

    def _dag_run_batch(self, dag, rngs, io):
        return self._run_stages(dag, rngs, True, io)

    @staticmethod
    def _spilled(io: _Traffic, inputs, call: Callable, download: bool = True,
                 **ids) -> Any:
        """One stage between host copies, under a ``hadoop.stage`` span
        (``ids`` name it): upload ``inputs`` (a pytree of host arrays),
        ``call`` them, wait for the device, and copy the output back to
        the host unless ``download`` is off."""
        with _span("hadoop.stage", **ids):
            with _span("hadoop.h2d"):
                args = jax.tree_util.tree_map(io.up, inputs)
            with _span("hadoop.dispatch"):
                out = call(*args)
                if download:
                    # queue the copy behind the stage, as a bare np.asarray
                    # would, so the wait below adds no host round trip
                    # before the copy starts
                    out.copy_to_host_async()
            with _span("hadoop.wait"):
                jax.block_until_ready(out)
            if not download:
                return out
            with _span("hadoop.d2h"):
                return io.down(out)

    def _dag_run_population(self, dag, rng, dynb, n, io, bucket_size=None):
        """Staged population sweep over the plan's bucket schedule: every
        candidate's intermediates spill through host memory per *fused
        stage* (the population multiplies the "HDFS" traffic — at stage,
        not edge, granularity), each bucket executing its stratum in one
        vmapped call per stage so the staged trip bounds follow the
        bucket's own maxima.  Sources are generated once and shared —
        candidates differ only in dynamic params, so source nodes stay
        unbatched until a stage first writes a node."""
        with _span("hadoop.init"):
            plan = plans.lower(dag)
            sched = plan.bucket_schedule(dynb, bucket_size)
            nb = sched.bucket_size
            init, stages, finalize = plan.stages_parametric()
            pkey = plan.structure_key()
            src_key = tuple(sorted(plan.sources.items()))
            jinit = self._cached_stage(("init", False, src_key),
                                       lambda: init)
            shared = {k: io.down(v)                  # shared "HDFS read"
                      for k, v in jinit(rng).items()}
            io.io += sum(h.nbytes for h in shared.values())
        out_np: Optional[np.ndarray] = None
        for bi, b in enumerate(sched.buckets):
            sub = _take_candidates(dynb, b.indices, io)
            stage_dyns = plan.stage_dyn_tuples(sub)
            nodes: Dict[str, np.ndarray] = dict(shared)
            batched: Dict[str, bool] = {}
            for si, (srcs, dst, stage, stage_key) in enumerate(stages):
                prev = nodes.get(dst)
                x_axes = tuple(0 if batched.get(s) else None for s in srcs)
                prev_ax = 0 if batched.get(dst) else None

                def call(xs, prev, stage=stage, stage_key=stage_key,
                         xa=x_axes, pa=prev_ax, dyn=stage_dyns[si]):
                    hp = prev is None
                    sfn = self._cached_stage(
                        ("pstage", nb, xa, hp, pa, stage_key),
                        lambda: jax.vmap(stage, in_axes=(
                            None, list(xa), None if hp else pa, 0)))
                    return sfn(rng, xs, prev, dyn)

                host = self._spilled(io, ([nodes[s] for s in srcs], prev),
                                     call, bucket=bi, stage=si)
                io.io += host.nbytes * 2.0           # write + read back
                nodes[dst] = host
                batched[dst] = True
            fin_axes = {k: 0 if batched.get(k) else None for k in nodes}
            jfin = self._cached_stage(
                ("pfinalize", nb, tuple(sorted(fin_axes.items())), pkey),
                lambda ax=fin_axes: jax.vmap(finalize, in_axes=(ax,)))
            host = self._spilled(io, (nodes,), jfin, bucket=bi,
                                 stage="finalize")
            if out_np is None:
                out_np = np.empty((sched.n,) + host.shape[1:], host.dtype)
            out_np[b.indices[:b.valid]] = host[:b.valid]
        return io.up(out_np)

    def _cached_stage(self, key: Tuple, make: Callable,
                      cost: float = 0.0) -> Callable:
        # staged executables share this instance's pool domain with the
        # whole-plan executables (keys cannot collide: stage keys lead
        # with a string tag), so the eviction cap bounds both together
        def build() -> Callable:
            def counted(*args, _f=make()):
                CACHE_STATS["traces"] += 1
                return _f(*args)

            return jax.jit(counted)

        return get_pool().get(self.exec_domain(), self._exec_key(key), build,
                              cost=cost)

    def _run_stages(self, dag: ProxyDAG, rng: jax.Array, vmap: bool,
                    io: _Traffic) -> Any:
        """Stage-by-stage execution with host-spilled intermediates at
        *fused-stage* granularity: a fused chain of low-weight edges
        spills once, not once per edge — the plan lowering cuts the
        "HDFS" round-trip volume.  Each stage's jitted form is cached
        under its structural key, so repeated runs — and dynamic-param
        sweeps — reuse every per-stage compile."""
        with _span("hadoop.init"):
            plan = plans.lower(dag)
            init, stages, finalize = plan.stages_parametric()
            pkey = plan.structure_key()
            stage_dyns = plan.stage_dyn_tuples(dag.dynamic_params())
            src_key = tuple(sorted(plan.sources.items()))
            jinit = self._cached_stage(
                ("init", vmap, src_key),
                lambda: jax.vmap(init) if vmap else init)
            nodes: Dict[str, np.ndarray] = {   # "HDFS read" of inputs
                k: io.down(v) for k, v in jinit(rng).items()}
            io.io += sum(h.nbytes for h in nodes.values())
        for si, (srcs, dst, stage, stage_key) in enumerate(stages):  # map tasks
            prev = nodes.get(dst)

            def call(xs, prev, si=si, stage=stage, stage_key=stage_key):
                hp = prev is None
                sfn = self._cached_stage(
                    ("stage", vmap, hp, stage_key),
                    lambda: (jax.vmap(stage, in_axes=(0, 0, None if hp
                                                      else 0, None))
                             if vmap else stage),
                    cost=float(plan.stages[si].cost))
                return sfn(rng, xs, prev, stage_dyns[si])

            host = self._spilled(io, ([nodes[s] for s in srcs], prev), call,
                                 stage=si)           # spill to "disk"
            io.io += host.nbytes * 2.0               # write + read back
            nodes[dst] = host
        jfin = self._cached_stage(
            ("finalize", vmap, pkey),
            lambda: jax.vmap(finalize) if vmap else finalize)
        return self._spilled(io, (nodes,), jfin, download=False,
                             stage="finalize")

    # -- seed-compatible chunked map/reduce ---------------------------------

    def map_reduce(self, map_fn: Callable, reduce_fn: Callable,
                   data: jax.Array, n_chunks: Optional[int] = None
                   ) -> RunReport:
        """Chunked map -> host-spilled shuffle -> reduce (the seed's
        ``hadoop()`` execution shape, now reporting uniformly)."""
        n_chunks = n_chunks or self.n_chunks
        io = _Traffic()
        t0 = time.perf_counter()
        n = data.shape[0] // n_chunks * n_chunks
        chunks = io.down(data[:n]).reshape(n_chunks, -1, *data.shape[1:])
        jmap = jax.jit(map_fn)
        intermediates: List[np.ndarray] = []
        for c in chunks:                              # map tasks
            host = io.down(jmap(io.up(c)))            # spill to "disk"
            io.io += host.nbytes * 2.0                # write + read back
            intermediates.append(host)
        shuffled = io.up(
            np.concatenate([i.reshape(-1) for i in intermediates]))
        result = jax.jit(reduce_fn)(shuffled)         # reduce task
        jax.block_until_ready(result)
        return io.report(self.name, time.perf_counter() - t0, result, 1)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


_STACKS: Dict[str, Stack] = {}


def register_stack(stack: Stack) -> Stack:
    """Register a Stack instance under its ``name``."""
    _STACKS[stack.name] = stack
    return stack


def get_stack(name: str) -> Stack:
    """Look up a registered software stack (``KeyError`` on unknown)."""
    if name not in _STACKS:
        raise KeyError(f"unknown stack {name!r}; known: {sorted(_STACKS)}")
    return _STACKS[name]


def list_stacks() -> List[str]:
    """Registered stack names, sorted."""
    return sorted(_STACKS)


register_stack(OpenMPStack())
register_stack(MPIStack())
register_stack(SparkStack())
register_stack(HadoopStack())
