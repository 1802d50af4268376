"""Proxy serving engine: concurrent request streams with tail-latency SLOs
and a fault-tolerance layer.

The paper's proxies stand in for production big-data services, and Gao et
al. (arXiv 1802.00699) frame dwarf proxies explicitly as *service-level*
workload mimics — but a benchmark that only ever executes one proxy at a
time cannot report the metrics a service is judged by: latency
percentiles under load, time to first result, sustained throughput,
behavior under failure.  This module closes that gap on top of the
compile-once/run-many machinery:

* A request **queue** admits heterogeneous :class:`ProxyRequest`\\ s (any
  structure + per-request dynamic params + per-request rng + optional
  per-request latency ``deadline_s`` and SLO class) and groups them by
  compiled identity — ``(stack, plan.structure_key())`` — into
  per-structure FIFO lanes.
* The dispatch loop drains the most urgent lane (earliest absolute
  deadline first, oldest head otherwise) into a **micro-batch** (up to
  ``max_batch`` requests), stratifies it by the engine cost model, and
  executes it in fixed-size chunks through the stack's cached serve
  executables (``Stack._compiled_plan_serve`` — one vmapped call per
  chunk, every request its own rng/params lane).  Chunk sizes never vary
  (the tail pads by repeating its last request), so steady-state serving
  is **zero retraces**, at most one compile per new (structure, chunk
  size) — and :meth:`ServingEngine.warmup` pre-pays even those through
  the :class:`~repro.core.pool.ExecutablePool`.
* ``batch_wait_s`` sets the **partial-chunk timeout flush** policy: ``0``
  dispatches eagerly (the default), ``inf`` holds a lane until a full
  chunk accumulates, and a finite positive value holds at most that long
  before flushing a short padded chunk — bounding the price a lone
  request pays for batching instead of holding P99 hostage.
* Every request's queue wait, service time, total latency and terminal
  status are recorded; the :class:`ServeReport` emits P50/P95/P99, time
  to first result, sustained throughput, the micro-batch histogram,
  cold-dispatch / retry / deadline-miss / degradation accounting and a
  :class:`ResourceMonitor` host/device-memory summary.

Fault tolerance (the resilience layer):

* A seeded :class:`repro.faults.FaultPlan` injects executor failures,
  stragglers and pool-eviction storms at chosen request indices —
  honored identically under both clocks, so chaos runs are
  bit-reproducible.
* Failed chunks **retry** with capped exponential backoff; a chunk that
  fails again is **bisected** so a poison request is isolated instead of
  failing its whole batch.  Real (non-injected) dispatch failures also
  invalidate the chunk's pooled executable (it may itself be the fault).
* A per-``(stack, structure)`` **circuit breaker** trips after repeated
  failures and degrades that lane — requests serve singly through the
  stock XLA lowering (:func:`repro.kernels.dispatch.forced_backend`)
  until enough degraded dispatches succeed to close the breaker again.
  Every degraded dispatch is counted; no request is ever lost — each
  reaches a terminal status (``ok`` / ``retried`` / ``degraded`` /
  ``failed``).

Live submission: :meth:`ServingEngine.start` turns the grouping loop
into a long-lived dispatcher thread; :meth:`ServingEngine.submit` admits
requests from any number of concurrent threads and returns a
``concurrent.futures.Future`` per request; :meth:`ServingEngine.drain`
blocks until the queues empty and :meth:`ServingEngine.shutdown` joins
the service and returns the session's :class:`ServeReport`.

Two clocks make runs comparable and CI-gateable:

* ``clock="wall"`` executes for real; service times are measured.
* ``clock="virtual"`` never executes — service times come from the
  engine's deterministic per-candidate cost model
  (:meth:`ExecutionPlan.candidate_costs`), so the same trace (and the
  same fault plan) yields bit-identical percentiles on any machine, any
  number of times.  The queue dynamics (admission order, grouping,
  batching, retries, degradation) are exactly the wall-clock loop's.

Arrival traces are seeded and deterministic: :func:`poisson_trace` (open
loop — arrivals don't wait for completions) and :func:`burst_trace`
(synchronized waves; ``bursts=1`` is the capacity test where everything
arrives at once).  ``mode="closed"`` serves any trace closed-loop: each
request is admitted only when the previous one completes — the
sequential baseline micro-batching is judged against.
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..api.stack import CACHE_STATS, Stack, classify_failure, get_stack
from ..core import schedule as plans
from ..core.dag import ProxyDAG
from ..core.pool import ExecutablePool, get_pool
from ..faults import FaultPlan, InjectedFailure
from ..kernels.dispatch import forced_backend, megakernel_enabled

#: virtual-clock calibration: modeled cost units (flops + vpu + bytes)
#: retired per second, plus a fixed per-dispatch overhead — the absolute
#: scale is arbitrary; percentile *structure* under the queueing dynamics
#: is what the deterministic clock exists for
VIRTUAL_RATE = 5.0e10
VIRTUAL_OVERHEAD_S = 2.0e-4
#: modeled compile cost a virtual-clock dispatch pays when its executable
#: is cold (post eviction-storm chaos, or a degraded form's first use)
VIRTUAL_COLD_S = 2.0e-2

#: terminal per-request statuses (every request reaches exactly one)
STATUSES = ("ok", "retried", "degraded", "failed")


# ---------------------------------------------------------------------------
# requests + traces
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ProxyRequest:
    """One admission: a proxy structure, its dynamic params, its rng."""

    rid: int                   # position in the trace (result ordering)
    structure: str             # spec name / label (reporting only)
    dag: ProxyDAG              # shared per-structure template
    dyn: Any                   # unbatched dynamic_params()-shaped pytree
    rng: jax.Array
    arrival_s: float           # arrival offset from trace start
    #: latency budget relative to arrival; completion later than
    #: ``arrival_s + deadline_s`` counts a deadline miss (never a drop)
    deadline_s: Optional[float] = None
    slo: str = "standard"      # SLO class label (deadline-miss breakdown)

    @property
    def abs_deadline(self) -> float:
        """Absolute deadline (inf when the request declared none) — the
        earliest-deadline-first lane-selection key."""
        if self.deadline_s is None:
            return math.inf
        return self.arrival_s + self.deadline_s


@dataclasses.dataclass
class ArrivalTrace:
    """A deterministic, seeded request stream."""

    name: str
    seed: int
    requests: List[ProxyRequest]

    def __len__(self) -> int:
        return len(self.requests)

    def __iter__(self):
        return iter(self.requests)

    @property
    def structures(self) -> List[str]:
        return sorted({r.structure for r in self.requests})

    def unique_dags(self) -> List[ProxyDAG]:
        """One template per distinct structure — the warmup working set."""
        seen, dags = set(), []
        for r in self.requests:
            key = r.dag.canonical_structure_key()
            if key not in seen:
                seen.add(key)
                dags.append(r.dag)
        return dags


def _templates(mix: Optional[Sequence[str]]):
    """(name, dag, space, base_values) per spec in the request mix."""
    from ..api.params import ParamSpace
    from ..api.spec import ProxySpec
    from ..core.workloads import PROXY_SPECS
    names = tuple(mix) if mix else tuple(sorted(PROXY_SPECS))
    out = []
    for name in names:
        if name not in PROXY_SPECS:
            raise KeyError(f"unknown proxy spec {name!r}; known: "
                           f"{sorted(PROXY_SPECS)}")
        dag = ProxySpec.from_json(PROXY_SPECS[name]).to_benchmark().dag
        space = ParamSpace.from_dag(dag)
        out.append((name, dag, space, space.values(dag)))
    return out


def _make_request(i: int, tmpl, seed: int, arrival: float,
                  deadline_s: Optional[float] = None,
                  slo: str = "standard") -> ProxyRequest:
    name, dag, space, base = tmpl
    row = space.sample_dynamic(1, base, seed=seed + 7919 * i)[0]
    dynb = space.stack_candidates(dag, row[None])
    dyn = jax.tree_util.tree_map(lambda v: v[0], dynb)
    return ProxyRequest(
        rid=i, structure=name, dag=dag, dyn=dyn,
        rng=jax.random.fold_in(jax.random.PRNGKey(seed), i),
        arrival_s=float(arrival), deadline_s=deadline_s, slo=slo)


def poisson_trace(n: int = 32, rate_rps: float = 100.0, seed: int = 0,
                  mix: Optional[Sequence[str]] = None,
                  deadline_s: Optional[float] = None,
                  slo: str = "standard") -> ArrivalTrace:
    """Open-loop Poisson arrivals at ``rate_rps``, request mix drawn
    uniformly from ``mix`` (default: every ``PROXY_SPECS`` proxy), every
    request's dynamic params independently sampled from its structure's
    :class:`~repro.api.params.ParamSpace` — all under one seed, so the
    trace is bit-reproducible across processes and machines.
    ``deadline_s``/``slo`` stamp every request with a latency budget and
    SLO class for deadline-miss accounting."""
    rs = np.random.RandomState(seed)
    arrivals = np.cumsum(rs.exponential(1.0 / max(rate_rps, 1e-9), size=n))
    tmpl = _templates(mix)
    picks = rs.randint(0, len(tmpl), size=n)
    return ArrivalTrace(
        name=f"poisson:n={n}:rate={rate_rps:g}:seed={seed}", seed=seed,
        requests=[_make_request(i, tmpl[picks[i]], seed, arrivals[i],
                                deadline_s, slo)
                  for i in range(n)])


def burst_trace(n: int = 32, bursts: int = 4, period_s: float = 0.05,
                seed: int = 0,
                mix: Optional[Sequence[str]] = None,
                deadline_s: Optional[float] = None,
                slo: str = "standard") -> ArrivalTrace:
    """Synchronized arrival waves: ``n`` requests split evenly across
    ``bursts`` bursts ``period_s`` apart (every member of a burst arrives
    at the same instant — the tail-latency stressor Poisson smoothing
    hides).  ``bursts=1`` is the capacity trace: everything at t=0."""
    rs = np.random.RandomState(seed)
    tmpl = _templates(mix)
    picks = rs.randint(0, len(tmpl), size=n)
    per = max(1, -(-n // max(bursts, 1)))        # ceil split
    return ArrivalTrace(
        name=f"burst:n={n}:bursts={bursts}:seed={seed}", seed=seed,
        requests=[_make_request(i, tmpl[picks[i]], seed,
                                (i // per) * period_s, deadline_s, slo)
                  for i in range(n)])


# ---------------------------------------------------------------------------
# resource monitor
# ---------------------------------------------------------------------------


def _page_size() -> int:
    try:
        return os.sysconf("SC_PAGE_SIZE")
    except (AttributeError, ValueError, OSError):  # pragma: no cover
        return 4096


class ResourceMonitor(threading.Thread):
    """Daemon thread sampling host RSS (``/proc/self/statm``) and device
    memory (``Device.memory_stats``, where the backend exposes it) while a
    serve runs — no psutil dependency, negligible overhead."""

    def __init__(self, interval_s: float = 0.005):
        super().__init__(daemon=True)
        self.interval_s = interval_s
        self._halt = threading.Event()
        self.host_rss: List[int] = []
        self.device_bytes: List[int] = []

    def _sample(self) -> None:
        try:
            with open("/proc/self/statm") as f:
                self.host_rss.append(
                    int(f.read().split()[1]) * _page_size())
        except (OSError, ValueError, IndexError):  # pragma: no cover
            pass
        ms = jax.local_devices()[0].memory_stats()   # None on CPU
        if ms and "bytes_in_use" in ms:
            self.device_bytes.append(int(ms["bytes_in_use"]))

    def run(self) -> None:
        while not self._halt.is_set():
            self._sample()
            self._halt.wait(self.interval_s)

    def stop(self) -> Dict[str, float]:
        """Idempotent stop+join+summarize: safe to call from a
        ``finally`` even if the monitor already stopped."""
        self._halt.set()
        if self.is_alive():
            self.join(timeout=2.0)
        self._sample()              # at least one sample, however short
        out: Dict[str, float] = {
            "samples": float(len(self.host_rss)),
            "host_rss_peak_bytes": float(max(self.host_rss, default=0)),
            "host_rss_mean_bytes": float(np.mean(self.host_rss))
            if self.host_rss else 0.0,
        }
        if self.device_bytes:
            out["device_peak_bytes"] = float(max(self.device_bytes))
        return out


# ---------------------------------------------------------------------------
# ServeReport
# ---------------------------------------------------------------------------


def _percentiles(xs: Sequence[float]) -> Dict[str, float]:
    if not xs:
        return {k: 0.0 for k in ("p50", "p95", "p99", "mean", "max")}
    a = np.asarray(xs, np.float64)
    return {"p50": float(np.percentile(a, 50)),
            "p95": float(np.percentile(a, 95)),
            "p99": float(np.percentile(a, 99)),
            "mean": float(a.mean()), "max": float(a.max())}


@dataclasses.dataclass
class ServeReport:
    """Uniform result of one served trace / live session — the SLO and
    resilience surface."""

    stack: str
    clock: str                      # "wall" | "virtual"
    mode: str                       # "open" | "closed" | "live"
    n_requests: int
    structures: int                 # distinct compiled groups served
    makespan_s: float               # first arrival -> last completion
    throughput_rps: float           # n_requests / makespan
    time_to_first_result_s: float
    latency_s: Dict[str, float]     # p50/p95/p99/mean/max end-to-end
    queue_wait_s: Dict[str, float]  # arrival -> dispatch start
    service_s: Dict[str, float]     # dispatch chunk execution
    batch_hist: Dict[int, int]      # micro-batch size -> dispatch count
    dispatches: int                 # executable calls (chunks)
    cold_dispatches: int            # chunks that compiled first
    compile_s: float                # wall time of cold chunks (compile-
                                    # inclusive service; 0 when warm)
    retraces: int                   # CACHE_STATS trace delta (wall clock)
    resources: Dict[str, float]
    # -- resilience accounting (PR 7) ---------------------------------------
    failures: int = 0               # failed dispatch attempts observed
    retries: int = 0                # chunk re-dispatches after a failure
    deadline_misses: int = 0        # completions past their budget
    deadline_miss_by_slo: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    degraded_dispatches: int = 0    # dispatches served under open breaker
    breaker_trips: int = 0          # circuit-breaker open transitions
    timeout_flushes: int = 0        # partial chunks flushed by batch_wait
    lost_requests: int = 0          # requests with no terminal status
                                    # (the zero-loss invariant: always 0)
    #: per-request terminal status in trace order ("ok" | "retried" |
    #: "degraded" | "failed")
    statuses: List[str] = dataclasses.field(default_factory=list,
                                            repr=False)
    fault_plan: Dict[str, int] = dataclasses.field(default_factory=dict)
    #: per-request host results in trace order (bit-identity checks);
    #: empty under the virtual clock, ``None`` for failed requests
    results: List[Any] = dataclasses.field(default_factory=list, repr=False)
    #: requests served per structure name — with :attr:`templates` this
    #: lets ``repro.api.fingerprint(report)`` distill the mix's aggregate
    #: channel vector without re-running the trace
    structure_mix: Dict[str, int] = dataclasses.field(default_factory=dict)
    #: structure name -> its ProxyDAG template (not serialized)
    templates: Dict[str, Any] = dataclasses.field(default_factory=dict,
                                                  repr=False)

    def status_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for s in self.statuses:
            out[s] = out.get(s, 0) + 1
        return out

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(dataclasses.replace(self, templates={}))
        d.pop("results")
        d.pop("statuses")
        d.pop("templates")
        d["status_counts"] = self.status_counts()
        d["batch_hist"] = {str(k): v
                           for k, v in sorted(self.batch_hist.items())}
        return d


# ---------------------------------------------------------------------------
# circuit breaker + per-run session state
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CircuitBreaker:
    """Per-``(stack, structure)`` failure gate.

    ``closed`` = normal dispatch.  After ``threshold`` consecutive
    failures it ``open``\\ s: the lane degrades (singleton dispatches
    through the forced-XLA fallback) until ``recovery`` consecutive
    degraded dispatches succeed, which closes it again.  A failure while
    open resets the recovery progress."""

    threshold: int = 3
    recovery: int = 4
    state: str = "closed"
    consecutive_failures: int = 0
    successes_while_open: int = 0
    trips: int = 0

    @property
    def open(self) -> bool:
        return self.state == "open"

    def record_failure(self) -> bool:
        """Count a failure; returns True when this failure trips the
        breaker open."""
        self.consecutive_failures += 1
        self.successes_while_open = 0
        if self.state == "closed" \
                and self.consecutive_failures >= self.threshold:
            self.state = "open"
            self.trips += 1
            return True
        return False

    def record_success(self) -> None:
        self.consecutive_failures = 0
        if self.state == "open":
            self.successes_while_open += 1
            if self.successes_while_open >= self.recovery:
                self.state = "closed"
                self.successes_while_open = 0


class _Session:
    """Mutable accounting for one serve() run or one live session."""

    def __init__(self, execute: bool, closed: bool,
                 faults: Optional[FaultPlan]):
        self.execute = execute
        self.closed = closed
        self.faults = faults if faults is not None else FaultPlan()
        self.lat: Dict[int, float] = {}
        self.qwait: Dict[int, float] = {}
        self.svc: Dict[int, float] = {}
        self.results: Dict[int, Any] = {}
        self.statuses: Dict[int, str] = {}
        self.errors: Dict[int, str] = {}
        self.costs: Dict[int, float] = {}
        self.attempts: Dict[int, int] = {}
        self.batch_hist: Dict[int, int] = {}
        self.breakers: Dict[Tuple, CircuitBreaker] = {}
        self.dispatches = 0
        self.cold_dispatches = 0
        self.compile_s = 0.0
        self.failures = 0
        self.retries = 0
        self.degraded_dispatches = 0
        self.timeout_flushes = 0
        self.deadline_misses = 0
        self.deadline_miss_by_slo: Dict[str, int] = {}
        self.first_done: Optional[float] = None
        #: virtual-clock executable-cache model: before the first eviction
        #: storm every dispatch is warm (warmup pre-paid the compiles);
        #: after a storm, each executable pays :data:`VIRTUAL_COLD_S` once
        #: to re-warm — the deterministic analog of the wall-clock
        #: recompile
        self.virtual_warm: set = set()
        self.virtual_storms = 0
        self.evicted_rids: set = set()
        self.traces0 = CACHE_STATS["traces"]


# ---------------------------------------------------------------------------
# ServingEngine
# ---------------------------------------------------------------------------


class _LiveState:
    """Book-keeping of one start()/shutdown() live-serving session."""

    def __init__(self, engine: "ServingEngine"):
        self.lock = threading.Lock()
        self.cond = threading.Condition(self.lock)
        self.groups: Dict[Tuple, Dict[str, Any]] = {}
        self.session = _Session(execute=True, closed=False,
                                faults=engine.faults)
        self.futures: Dict[int, Future] = {}
        self.monitor = ResourceMonitor()
        self.thread: Optional[threading.Thread] = None
        self.t0 = time.perf_counter()
        self.next_rid = 0
        self.inflight = 0            # submitted but not yet resolved
        self.stopping = False
        self.first_arrival: Optional[float] = None
        self.last_done = 0.0


class ServingEngine:
    """Continuous micro-batching over one software stack, with retries,
    deadlines, graceful degradation and live submission.

    ``max_batch`` bounds how many same-structure requests one dispatch
    drains; ``bucket_size`` pins the executable chunk size (default: the
    population policy — one lane per device, so a single-device CPU
    serves unbatched parametric calls and a mesh fills its device axis);
    ``batch_wait_s`` sets the partial-chunk flush policy (0 = dispatch
    eagerly, ``inf`` = hold for full chunks, finite = flush after that
    wait).  ``faults`` installs a default :class:`~repro.faults.FaultPlan`
    for every serve/live session; retry and circuit-breaker knobs
    configure the resilience layer.  All compiled artifacts live in the
    shared :class:`ExecutablePool`; :meth:`warmup` pre-compiles a
    declared working set so the first request is served warm."""

    def __init__(self, stack: Union[str, Stack] = "openmp",
                 max_batch: int = 8, bucket_size: Optional[int] = None,
                 pool: Optional[ExecutablePool] = None,
                 batch_wait_s: float = 0.0,
                 faults: Optional[FaultPlan] = None,
                 max_retries: int = 3,
                 backoff_base_s: float = 1.0e-3,
                 backoff_cap_s: float = 5.0e-2,
                 breaker_threshold: int = 3,
                 breaker_recovery: int = 4):
        self.stack = get_stack(stack) if isinstance(stack, str) else stack
        self.max_batch = max(1, int(max_batch))
        self.bucket_size = bucket_size
        self.pool = pool if pool is not None else get_pool()
        self.batch_wait_s = max(0.0, float(batch_wait_s))
        self.faults = faults
        self.max_retries = max(0, int(max_retries))
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.breaker_threshold = max(1, int(breaker_threshold))
        self.breaker_recovery = max(1, int(breaker_recovery))
        self._live: Optional[_LiveState] = None

    # -- sizing --------------------------------------------------------------

    def _chunk_size(self) -> int:
        """The fixed executable chunk size.  Fixed — never shrunk to a
        small batch (tails pad instead) — so the steady state needs
        exactly one executable per (structure, size)."""
        if self.bucket_size is not None:
            return max(1, min(int(self.bucket_size), self.max_batch))
        return max(1, min(plans.resolve_bucket_size(self.max_batch),
                          self.max_batch))

    # -- warmup --------------------------------------------------------------

    def warmup(self, specs, bucket_sizes: Optional[Tuple[int, ...]] = None
               ) -> Dict[str, int]:
        """Pre-compile the working set: every distinct structure in
        ``specs`` (an :class:`ArrivalTrace`, request list, or
        DAG/spec iterable) at this engine's chunk sizes — after which a
        serve of those structures starts at zero retraces."""
        if isinstance(specs, ArrivalTrace):
            specs = specs.unique_dags()
        else:
            specs = list(specs)
            if specs and isinstance(specs[0], ProxyRequest):
                specs = ArrivalTrace("adhoc", 0, specs).unique_dags()
        if bucket_sizes is None:
            bucket_sizes = (1, self._chunk_size())
        return self.pool.warmup(specs, stack=self.stack,
                                bucket_sizes=bucket_sizes)

    # -- group bookkeeping ---------------------------------------------------

    def _group_for(self, groups: Dict[Tuple, Dict[str, Any]],
                   r: ProxyRequest) -> Tuple:
        """Ensure ``r``'s compiled-identity group exists; returns its key."""
        plan = plans.lower_population(r.dag)
        gkey = (self.stack.name, plan.structure_key())
        if gkey not in groups:
            groups[gkey] = {"plan": plan, "queue": deque(), "remaining": 0}
        return gkey

    def _cost_of(self, plan, r: ProxyRequest) -> float:
        dynb1 = jax.tree_util.tree_map(lambda v: np.asarray(v)[None], r.dyn)
        c, _ = plan.candidate_costs(dynb1)
        return float(c[0])

    def _breaker(self, sess: _Session, gkey: Tuple) -> CircuitBreaker:
        br = sess.breakers.get(gkey)
        if br is None:
            br = CircuitBreaker(threshold=self.breaker_threshold,
                                recovery=self.breaker_recovery)
            sess.breakers[gkey] = br
        return br

    # -- dispatch ------------------------------------------------------------

    def _attempt(self, sess: _Session, g: Dict[str, Any],
                 chunk: List[ProxyRequest], valid: int, b: int,
                 degraded: bool) -> Tuple[float, bool, List]:
        """Execute (or, under the virtual clock, model) one fixed-size
        chunk.  Returns ``(service_s, was_cold, per-request results)``.
        ``degraded`` forces the stock XLA lowering (its executables cache
        under their own backend-tagged keys)."""
        stack = self.stack
        plan = g["plan"]
        if not degraded:
            # injected failures are decided *before* execution (the
            # executor "dies" mid-batch); attempts were already counted
            failing = [r for r in chunk[:valid]
                       if sess.faults.should_fail(r.rid,
                                                  sess.attempts[r.rid] - 1)]
            if failing:
                raise InjectedFailure(
                    f"injected executor failure for rids "
                    f"{sorted(r.rid for r in failing)}")
        if not sess.execute:
            # warm-form identity mirrors Stack._exec_key: backend tag +
            # the megakernel arming flag (a flag flip mid-session is a
            # different compiled form, so it must model cold)
            wkey = (g["plan"].structure_key(), b,
                    "xla" if degraded else None, megakernel_enabled())
            cold = sess.virtual_storms > 0 and wkey not in sess.virtual_warm
            sess.virtual_warm.add(wkey)
            service = (max(sess.costs[r.rid] for r in chunk[:valid])
                       / VIRTUAL_RATE + VIRTUAL_OVERHEAD_S
                       + (VIRTUAL_COLD_S if cold else 0.0))
            return service, cold, []
        m0 = stack.exec_domain().stats["misses"]
        t0 = time.perf_counter()
        with forced_backend("xla" if degraded else None):
            if b == 1:
                fn = stack._compiled_plan(plan, batch=False)
                r = chunk[0]
                # copy the dyn scalars: the batch=False form donates its
                # dyn buffers on accelerators, and a trace may be replayed
                dyn = jax.tree_util.tree_map(jnp.array, r.dyn)
                out, _ = stack._population_call(fn, r.rng, dyn)
            else:
                fn = stack._compiled_plan_serve(plan, b)
                rngs = jnp.stack([r.rng for r in chunk])
                dynb = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                    *[r.dyn for r in chunk])
                out = stack._serve_call(fn, rngs, dynb)
            jax.block_until_ready(out)
        service = time.perf_counter() - t0
        was_cold = stack.exec_domain().stats["misses"] > m0
        host = np.asarray(out)
        results = ([host] if b == 1
                   else [host[j] for j in range(valid)])
        return service, was_cold, results

    def _storm(self, sess: _Session, chunk: List[ProxyRequest]) -> None:
        """Honor any pool-eviction storm scheduled on this chunk's rids:
        wall clock evicts the stack's real executables (the next dispatch
        recompiles), virtual clock forgets its warm set (the next
        dispatch models the cold cost) — identical dynamics per plan."""
        storm = [r.rid for r in chunk
                 if sess.faults.evicts(r.rid)
                 and r.rid not in sess.evicted_rids]
        if not storm:
            return
        sess.evicted_rids.update(storm)
        if sess.execute:
            self.pool.clear(self.stack.exec_domain().name)
        else:
            sess.virtual_storms += 1
            sess.virtual_warm.clear()

    def _invalidate_executable(self, plan, b: int) -> None:
        """Invalidate-on-failure: drop the pooled executable a real
        dispatch failure went through — it may itself be the fault — and
        record the failure against the domain's health stats."""
        stack = self.stack
        dom = stack.exec_domain()
        if b == 1:
            key = stack._exec_key(False, plan.structure_key())
        else:
            key = stack._exec_key(("serve", b), plan.structure_key())
        self.pool.invalidate(dom, key)

    def _record(self, sess: _Session, r: ProxyRequest, start: float,
                done_t: float, service: float, status: str) -> None:
        base = start if sess.closed else r.arrival_s
        sess.qwait[r.rid] = start - base
        sess.svc[r.rid] = service
        lat = done_t - base
        sess.lat[r.rid] = lat
        sess.statuses[r.rid] = status
        if sess.first_done is None and status != "failed":
            sess.first_done = done_t
        if r.deadline_s is not None and lat > r.deadline_s + 1e-12:
            sess.deadline_misses += 1
            sess.deadline_miss_by_slo[r.slo] = \
                sess.deadline_miss_by_slo.get(r.slo, 0) + 1

    def _serve_chunk(self, sess: _Session, g: Dict[str, Any], gkey: Tuple,
                     reqs: List[ProxyRequest], b: int, start: float
                     ) -> float:
        """Serve ``reqs`` (≤ ``b`` requests of one structure) with the
        full resilience policy: retry with capped exponential backoff,
        bisect a repeatedly-failing multi-request chunk to isolate the
        poison request, degrade under an open breaker.  Records terminal
        accounting for every request; returns elapsed seconds."""
        breaker = self._breaker(sess, gkey)
        elapsed = 0.0
        chunk_attempt = 0
        while True:
            degraded = breaker.open
            if degraded and len(reqs) > 1:
                # open breaker: serve singly through the fallback path
                for r in reqs:
                    elapsed += self._serve_chunk(sess, g, gkey, [r], b,
                                                 start + elapsed)
                return elapsed
            valid = len(reqs)
            b_eff = 1 if degraded else b
            chunk = list(reqs)
            while len(chunk) < b_eff:    # fixed chunk size: pad by
                chunk.append(chunk[-1])  # repeating the last request
            self._storm(sess, chunk[:valid])
            for r in chunk[:valid]:
                sess.attempts[r.rid] = sess.attempts.get(r.rid, 0) + 1
            straggle = max((sess.faults.straggler_delay_s(r.rid)
                            for r in chunk[:valid]), default=0.0)
            elapsed += straggle          # delayed dispatch (both clocks)
            try:
                service, was_cold, outs = self._attempt(
                    sess, g, chunk, valid, b_eff, degraded)
            except Exception as exc:
                cls = classify_failure(exc)
                sess.failures += 1
                self.pool.record_failure(self.stack.exec_domain())
                breaker.record_failure()
                if sess.execute and cls not in ("injected",):
                    self._invalidate_executable(g["plan"], b_eff)
                if len(reqs) == 1:
                    r = reqs[0]
                    if cls == "fatal" \
                            or sess.attempts[r.rid] > self.max_retries:
                        sess.errors[r.rid] = f"{cls}: {exc}"
                        self._record(sess, r, start, start + elapsed,
                                     0.0, "failed")
                        return elapsed
                elif chunk_attempt >= 1 or cls == "fatal":
                    # chunk failed again (or can never succeed as-is):
                    # bisect to isolate the poison request instead of
                    # failing the whole batch
                    mid = max(1, len(reqs) // 2)
                    elapsed += self._serve_chunk(sess, g, gkey, reqs[:mid],
                                                 b, start + elapsed)
                    elapsed += self._serve_chunk(sess, g, gkey, reqs[mid:],
                                                 b, start + elapsed)
                    return elapsed
                backoff = min(self.backoff_base_s * (2 ** chunk_attempt),
                              self.backoff_cap_s)
                elapsed += backoff
                sess.retries += 1
                chunk_attempt += 1
                continue
            # success
            breaker.record_success()
            sess.dispatches += 1
            if degraded:
                sess.degraded_dispatches += 1
            if was_cold:
                sess.cold_dispatches += 1
                sess.compile_s += service
            elapsed += service
            done_t = start + elapsed
            for j, r in enumerate(chunk[:valid]):
                status = ("degraded" if degraded
                          else "retried" if sess.attempts[r.rid] > 1
                          else "ok")
                self._record(sess, r, start, done_t, service, status)
                if outs:
                    sess.results[r.rid] = outs[j]
            return elapsed

    def _serve_batch(self, sess: _Session, g: Dict[str, Any], gkey: Tuple,
                     batch: List[ProxyRequest], b: int, start: float
                     ) -> float:
        """Serve one drained micro-batch: stratify by modeled cost so a
        chunk's vmapped lanes share a trip bound (cheap requests never
        wait out a straggler lane), then run each fixed-size chunk
        through the resilient dispatch path."""
        sess.batch_hist[len(batch)] = sess.batch_hist.get(len(batch), 0) + 1
        order = sorted(batch, key=lambda r: (sess.costs[r.rid], r.rid))
        elapsed = 0.0
        for c0 in range(0, len(order), b):
            elapsed += self._serve_chunk(sess, g, gkey, order[c0:c0 + b],
                                         b, start + elapsed)
        return elapsed

    # -- serving loop (trace replay, both clocks) ----------------------------

    def serve(self, trace: Union[ArrivalTrace, Sequence[ProxyRequest]],
              clock: str = "wall", mode: str = "open",
              faults: Optional[FaultPlan] = None) -> ServeReport:
        """Serve every request of ``trace`` and report the SLO metrics.

        ``clock="wall"`` executes and measures; ``clock="virtual"`` is the
        deterministic cost-model simulation (no execution, identical
        reports across runs — including under a ``faults`` plan).
        ``mode="open"`` admits requests at their trace arrival times;
        ``mode="closed"`` admits each request only when the previous
        completes (the sequential baseline — batch size is pinned to 1).
        ``faults`` overrides the engine's default fault plan for this
        run."""
        if clock not in ("wall", "virtual"):
            raise ValueError(f"clock must be 'wall' or 'virtual', "
                             f"got {clock!r}")
        if mode not in ("open", "closed"):
            raise ValueError(f"mode must be 'open' or 'closed', "
                             f"got {mode!r}")
        requests = list(trace.requests if isinstance(trace, ArrivalTrace)
                        else trace)
        execute = clock == "wall"
        closed = mode == "closed"
        sess = _Session(execute=execute, closed=closed,
                        faults=self.faults if faults is None else faults)

        # group requests by compiled identity; model per-request costs
        # once (the stratification and virtual-service key)
        groups: Dict[Tuple, Dict[str, Any]] = {}
        gkey_of: Dict[int, Tuple] = {}
        for r in requests:
            gkey = self._group_for(groups, r)
            gkey_of[r.rid] = gkey
            groups[gkey]["remaining"] += 1
            sess.costs[r.rid] = self._cost_of(groups[gkey]["plan"], r)

        monitor = ResourceMonitor()
        monitor.start()

        pending = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        first_arrival = pending[0].arrival_s if pending else 0.0
        b = 1 if closed else self._chunk_size()
        max_batch = 1 if closed else self.max_batch
        wait = 0.0 if closed else self.batch_wait_s

        now = first_arrival
        i_next = 0

        def admit(t: float) -> None:
            nonlocal i_next
            while (i_next < len(pending)
                   and pending[i_next].arrival_s <= t + 1e-12):
                r = pending[i_next]
                i_next += 1
                g = groups[gkey_of[r.rid]]
                g["queue"].append(r)
                g["remaining"] -= 1

        def urgency(k: Tuple) -> Tuple:
            head = groups[k]["queue"][0]
            return (head.abs_deadline, head.arrival_s, head.rid)

        try:
            while i_next < len(pending) or any(g["queue"]
                                               for g in groups.values()):
                if closed:
                    # closed loop: next request becomes ready the instant
                    # the previous completes — trace arrival is ignored
                    if not any(g["queue"] for g in groups.values()):
                        r = pending[i_next]
                        i_next += 1
                        g = groups[gkey_of[r.rid]]
                        g["queue"].append(r)
                        g["remaining"] -= 1
                else:
                    admit(now)
                nonempty = [k for k, g in groups.items() if g["queue"]]
                if not nonempty:
                    now = max(now, pending[i_next].arrival_s)
                    continue
                if wait > 0.0:
                    # partial-chunk flush policy: a lane is dispatchable
                    # when a full chunk waits, no future arrival can ever
                    # fill it, or its head has waited out the flush
                    # timeout — the P99 hostage bound
                    def ready(k: Tuple) -> bool:
                        g = groups[k]
                        return (len(g["queue"]) >= b
                                or g["remaining"] == 0
                                or now - g["queue"][0].arrival_s
                                >= wait - 1e-12)
                    ready_keys = [k for k in nonempty if ready(k)]
                    if not ready_keys:
                        flush_at = min(
                            groups[k]["queue"][0].arrival_s + wait
                            for k in nonempty)
                        next_arr = (pending[i_next].arrival_s
                                    if i_next < len(pending) else math.inf)
                        now = min(flush_at, next_arr)
                        continue
                else:
                    ready_keys = nonempty
                # drain the most urgent lane: earliest absolute deadline
                # first, oldest waiting head otherwise
                gkey = min(ready_keys, key=urgency)
                g = groups[gkey]
                if (wait > 0.0 and len(g["queue"]) < b
                        and g["remaining"] > 0
                        and now - g["queue"][0].arrival_s >= wait - 1e-12):
                    sess.timeout_flushes += 1
                k = min(max_batch, len(g["queue"]))
                batch = [g["queue"].popleft() for _ in range(k)]
                now += self._serve_batch(sess, g, gkey, batch, b, now)
        finally:
            # never leak the sampler thread, even on an exception
            resources = monitor.stop()
        return self._build_report(sess, requests, len(groups),
                                  first_arrival, now, clock, mode,
                                  resources)

    # -- report --------------------------------------------------------------

    def _build_report(self, sess: _Session,
                      requests: Sequence[ProxyRequest], n_groups: int,
                      first_arrival: float, end: float, clock: str,
                      mode: str, resources: Dict[str, float]
                      ) -> ServeReport:
        makespan = max(end - first_arrival, 0.0)
        n = len(requests)
        served = [r for r in requests if r.rid in sess.lat]
        lost = n - len(served)
        trips = sum(br.trips for br in sess.breakers.values())
        mix: Dict[str, int] = {}
        templates: Dict[str, Any] = {}
        for r in requests:
            mix[r.structure] = mix.get(r.structure, 0) + 1
            if r.dag is not None:
                templates.setdefault(r.structure, r.dag)
        return ServeReport(
            stack=self.stack.name, clock=clock, mode=mode, n_requests=n,
            structures=n_groups,
            makespan_s=makespan,
            throughput_rps=n / max(makespan, 1e-12),
            time_to_first_result_s=(sess.first_done - first_arrival
                                    if sess.first_done is not None else 0.0),
            latency_s=_percentiles([sess.lat[r.rid] for r in served]),
            queue_wait_s=_percentiles([sess.qwait[r.rid] for r in served]),
            service_s=_percentiles([sess.svc[r.rid] for r in served]),
            batch_hist=sess.batch_hist,
            dispatches=sess.dispatches,
            cold_dispatches=sess.cold_dispatches,
            compile_s=sess.compile_s,
            retraces=(CACHE_STATS["traces"] - sess.traces0
                      if sess.execute else 0),
            resources=resources,
            failures=sess.failures,
            retries=sess.retries,
            deadline_misses=sess.deadline_misses,
            deadline_miss_by_slo=dict(sess.deadline_miss_by_slo),
            degraded_dispatches=sess.degraded_dispatches,
            breaker_trips=trips,
            timeout_flushes=sess.timeout_flushes,
            lost_requests=lost,
            statuses=[sess.statuses.get(r.rid, "lost") for r in requests],
            fault_plan=sess.faults.summary(),
            structure_mix=mix,
            templates=templates,
            results=[sess.results.get(r.rid) for r in requests])

    # -- live submission (start / submit / drain / shutdown) -----------------

    def start(self) -> "ServingEngine":
        """Start the long-lived dispatcher: after this, concurrent
        threads may :meth:`submit` requests; the grouping loop serves
        them with the same micro-batching, flush, and resilience policy
        as trace replay.  Returns ``self`` for chaining."""
        if self._live is not None:
            raise RuntimeError("ServingEngine is already started")
        live = _LiveState(self)
        self._live = live
        live.monitor.start()
        live.thread = threading.Thread(target=self._live_loop, daemon=True)
        live.thread.start()
        return self

    def submit(self, request: Optional[ProxyRequest] = None, *,
               structure: Optional[str] = None,
               rng: Optional[jax.Array] = None,
               deadline_s: Optional[float] = None,
               slo: str = "standard") -> "Future":
        """Thread-safe live admission; returns a ``Future`` resolving to
        the request's host result (or raising its terminal error).

        Pass an explicit :class:`ProxyRequest` (its ``rid``/``arrival_s``
        are re-stamped by the engine), or name a ``structure`` from
        ``PROXY_SPECS`` to have the engine draw that proxy's dynamic
        params deterministically from the assigned rid."""
        live = self._live
        if live is None:
            raise RuntimeError("ServingEngine.submit before start(); call "
                               "start() (and ideally warmup()) first")
        with live.cond:
            if live.stopping:
                raise RuntimeError("ServingEngine is shutting down")
            rid = live.next_rid
            live.next_rid += 1
            arrival = time.perf_counter() - live.t0
            if request is None:
                if structure is None:
                    raise TypeError("submit() needs a ProxyRequest or a "
                                    "structure= spec name")
                tmpl = self._template(structure)
                request = _make_request(rid, tmpl, seed=0, arrival=arrival,
                                        deadline_s=deadline_s, slo=slo)
            else:
                request = dataclasses.replace(
                    request, rid=rid, arrival_s=arrival,
                    deadline_s=(request.deadline_s if deadline_s is None
                                else deadline_s),
                    slo=slo if slo != "standard" else request.slo)
            if rng is not None:
                request = dataclasses.replace(request, rng=rng)
            gkey = self._group_for(live.groups, request)
            g = live.groups[gkey]
            sess = live.session
            sess.costs[rid] = self._cost_of(g["plan"], request)
            fut: Future = Future()
            live.futures[rid] = fut
            live.inflight += 1
            if live.first_arrival is None:
                live.first_arrival = arrival
            g["queue"].append(request)
            live.cond.notify_all()
        return fut

    def _template(self, structure: str):
        cache = self.__dict__.setdefault("_template_cache", {})
        if structure not in cache:
            cache[structure] = _templates((structure,))[0]
        return cache[structure]

    def _live_loop(self) -> None:
        live = self._live
        sess = live.session
        b = self._chunk_size()
        wait = self.batch_wait_s

        while True:
            with live.cond:
                batch: List[ProxyRequest] = []
                gkey = None
                while True:
                    now = time.perf_counter() - live.t0
                    nonempty = [k for k, g in live.groups.items()
                                if g["queue"]]
                    if nonempty:
                        def ready(k: Tuple) -> bool:
                            g = live.groups[k]
                            return (wait <= 0.0 or live.stopping
                                    or len(g["queue"]) >= b
                                    or now - g["queue"][0].arrival_s
                                    >= wait - 1e-12)
                        ready_keys = [k for k in nonempty if ready(k)]
                        if ready_keys:
                            gkey = min(
                                ready_keys,
                                key=lambda k: (
                                    live.groups[k]["queue"][0].abs_deadline,
                                    live.groups[k]["queue"][0].arrival_s,
                                    live.groups[k]["queue"][0].rid))
                            g = live.groups[gkey]
                            if (wait > 0.0 and len(g["queue"]) < b
                                    and not live.stopping):
                                sess.timeout_flushes += 1
                            k = min(self.max_batch, len(g["queue"]))
                            batch = [g["queue"].popleft()
                                     for _ in range(k)]
                            break
                        flush_in = min(
                            live.groups[k]["queue"][0].arrival_s + wait
                            - now for k in nonempty)
                        live.cond.wait(max(min(flush_in, 0.05), 1e-4))
                        continue
                    if live.stopping:
                        return
                    live.cond.wait(0.05)
            start = time.perf_counter() - live.t0
            try:
                elapsed = self._serve_batch(sess, live.groups[gkey], gkey,
                                            batch, b, start)
            except BaseException as exc:  # defense in depth: the batch
                elapsed = 0.0             # path handles its own failures
                for r in batch:
                    sess.errors[r.rid] = f"dispatcher: {exc}"
                    self._record(sess, r, start, start, 0.0, "failed")
            with live.cond:
                live.last_done = max(live.last_done, start + elapsed)
                for r in batch:
                    fut = live.futures.pop(r.rid, None)
                    if fut is not None:
                        if sess.statuses.get(r.rid) == "failed":
                            fut.set_exception(RuntimeError(
                                sess.errors.get(r.rid,
                                                "request failed")))
                        else:
                            fut.set_result(sess.results.get(r.rid))
                    live.inflight -= 1
                live.cond.notify_all()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every submitted request has resolved (queues empty
        and no dispatch in flight).  Returns False on timeout."""
        live = self._live
        if live is None:
            return True
        with live.cond:
            return live.cond.wait_for(
                lambda: live.inflight == 0
                and not any(g["queue"] for g in live.groups.values()),
                timeout=timeout)

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> ServeReport:
        """Stop the dispatcher and return the live session's
        :class:`ServeReport`.  ``drain=True`` (default) serves everything
        already submitted first; ``drain=False`` fails pending requests'
        futures immediately.  The resource monitor is always joined —
        shutdown never leaks the sampler thread."""
        live = self._live
        if live is None:
            raise RuntimeError("ServingEngine.shutdown without start()")
        if drain:
            self.drain(timeout=timeout)
        with live.cond:
            live.stopping = True
            if not drain:
                for g in live.groups.values():
                    while g["queue"]:
                        r = g["queue"].popleft()
                        fut = live.futures.pop(r.rid, None)
                        if fut is not None:
                            fut.set_exception(
                                RuntimeError("engine shut down before "
                                             "dispatch"))
                        live.inflight -= 1
                        live.session.statuses.setdefault(r.rid, "failed")
                        live.session.errors[r.rid] = "shutdown"
            live.cond.notify_all()
        try:
            if live.thread is not None:
                live.thread.join(timeout=10.0)
        finally:
            resources = live.monitor.stop()
            self._live = None
        sess = live.session
        requests: List[ProxyRequest] = []
        # statuses/latencies index by rid; rebuild the admitted order
        for rid in range(live.next_rid):
            requests.append(ProxyRequest(
                rid=rid, structure="", dag=None, dyn=None, rng=None,
                arrival_s=0.0))
        first = live.first_arrival if live.first_arrival is not None else 0.0
        return self._build_report(sess, requests, len(live.groups),
                                  first, live.last_done, "wall", "live",
                                  resources)


# ---------------------------------------------------------------------------
# public entry point (repro.api.serve)
# ---------------------------------------------------------------------------


def serve(trace: Union[ArrivalTrace, Sequence[ProxyRequest]], *,
          stack: Union[str, Stack] = "openmp", clock: str = "wall",
          mode: str = "open", max_batch: int = 8,
          bucket_size: Optional[int] = None,
          batch_wait_s: float = 0.0,
          faults: Optional[FaultPlan] = None,
          warmup: bool = True, **engine_kw) -> ServeReport:
    """Serve a request stream end to end: build a :class:`ServingEngine`
    on ``stack``, optionally pre-compile the trace's working set, and
    return the :class:`ServeReport`.  ``faults`` injects a seeded
    :class:`~repro.faults.FaultPlan`; ``batch_wait_s`` sets the
    partial-chunk flush policy; other keyword args reach the engine
    (retry/backoff/breaker knobs)."""
    eng = ServingEngine(stack=stack, max_batch=max_batch,
                        bucket_size=bucket_size,
                        batch_wait_s=batch_wait_s, faults=faults,
                        **engine_kw)
    if warmup and clock == "wall":
        eng.warmup(trace)
    return eng.serve(trace, clock=clock, mode=mode)
