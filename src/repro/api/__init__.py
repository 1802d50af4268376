"""repro.api — the unified execution API of the dwarf methodology.

Four public surfaces, one contract:

* **Stack protocol** (:mod:`repro.api.stack`): ``get_stack(name).run(x)``
  executes any proxy DAG, workload, or raw fn on any software stack
  (openmp / mpi / spark / hadoop) and returns a uniform :class:`RunReport`;
  ``run_batch`` vmaps over rng batches and ``run_population`` evaluates a
  whole batch of dynamic-param candidates through the ExecutionPlan's
  weight-stratified bucket schedule (the batched-autotuning axis — one
  shared executable per bucket size, buckets sharded over the stack's
  mesh).
* **Versioned ProxySpec** (:mod:`repro.api.spec`): declarative,
  schema-validated JSON specs with a full ``to_json``/``from_json``
  round-trip.
* **Pytree parameter space** (:mod:`repro.api.params`): every tunable
  flattened into a named, bounded vector for the auto-tuner and for
  gradient-free vectorized tuners — ``sample``/``sample_dynamic`` draw
  candidate matrices, ``stack_candidates``/``unstack_candidates`` convert
  between matrices and the batched dyn pytrees the executables consume.
* **Distillation pipeline** (:mod:`repro.core.engine` /
  :mod:`repro.core.subset`): :func:`fingerprint` measures any workload —
  a jitted fn, a recorded :class:`RunReport`, a ``ServeReport`` — into
  the engine's channel basis; :func:`tune_structure` accepts the
  fingerprint directly as its target; :func:`subset_fingerprints` keeps
  the suite small by clustering fingerprints down to representatives.

Quickstart::

    from repro.api import ProxySpec, get_stack
    spec = ProxySpec.load("proxy_terasort.json")
    report = get_stack(spec.stack).run(spec)
    print(report.wall_s, report.io_bytes)

Distillation quickstart::

    from repro.api import fingerprint, tune_structure
    fp = fingerprint(my_step_fn, example_args)   # measure anything jitted
    result = tune_structure(seed_proxy, fp)      # synthesize its proxy
"""

from . import params as params  # imported first: no repro.core dependencies
from .params import (CORE_FIELDS, EXTRA_BOUNDS, FIELD_BOUNDS, INT_FIELDS,
                     ParamLeaf, ParamSpace, bounds_for)
from .spec import (SPEC_VERSION, ProxySpec, SpecError,
                   validate_fingerprint_json, validate_spec_json)
from .stack import (FAILURE_CLASSES, HadoopStack, MPIStack, OpenMPStack,
                    RunReport, SparkStack, Stack, cache_cap, cache_stats,
                    classify_failure, failure_is_retryable, get_stack,
                    list_stacks, register_stack, reset_cache_stats)
from ..core.dwarfs.statistic import (grouped_stats, reset_grouped_stats,
                                     reset_segment_max_stats,
                                     segment_max_stats)
from ..core.engine import (FINGERPRINT_CHANNELS, FINGERPRINT_VERSION,
                           WorkloadFingerprint, fingerprint)
from ..core.pool import ExecutablePool, get_pool, pool_stats
from ..core.subset import (SubsetReport, normalize_fingerprints,
                           subset_fingerprints)
from ..faults import FaultPlan, InjectedFailure, default_fault_rate


def tune_structure(proxy, target_metrics, **kw):
    """Tune the full Fig.-3 design space of ``proxy`` — structure *and*
    weights — toward ``target_metrics``.

    ``proxy`` may be a ``ProxyBenchmark``, ``ProxySpec``, or ``ProxyDAG``;
    ``target_metrics`` is either a hand-declared Table-3 metric dict or
    any measurement with a ``metrics()`` method — in particular a
    :class:`WorkloadFingerprint` from :func:`fingerprint`, which distills
    a proxy straight from a measured workload.  Keyword args configure
    :class:`repro.core.structsearch.StructuralTuner`
    (``max_candidates`` total budget, ``structure_budget_frac`` split,
    ``components`` mutation pool, ``seed_structures``, ...).  Returns a
    :class:`~repro.core.structsearch.StructuralTuneResult` whose ``proxy``
    holds the best machine-generated structure with tuned weights — ready
    for ``ProxySpec.from_benchmark`` serialization or any ``get_stack``
    execution."""
    from ..core.dag import ProxyDAG
    from ..core.proxy import ProxyBenchmark
    from ..core.structsearch import StructuralTuner
    if isinstance(proxy, ProxyDAG):
        proxy = ProxyBenchmark(dag=proxy)
    elif hasattr(proxy, "to_benchmark"):            # ProxySpec
        proxy = proxy.to_benchmark()
    return StructuralTuner(target_metrics, **kw).tune(proxy)


def serve(trace, **kw):
    """Serve a request stream through the proxy serving engine and return
    its :class:`~repro.serve.engine.ServeReport` (P50/P95/P99 latency,
    time to first result, sustained throughput, retrace accounting).

    ``trace`` is an :class:`~repro.serve.engine.ArrivalTrace` — build one
    with :func:`repro.serve.poisson_trace` / :func:`repro.serve.burst_trace`
    — or a plain request list; keyword args configure the engine
    (``stack``, ``max_batch``, ``bucket_size``, ``clock``, ``mode``,
    ``warmup``, ``batch_wait_s`` partial-chunk flush, ``faults`` — a
    seeded :class:`~repro.faults.FaultPlan` for chaos runs — plus the
    retry/backoff/circuit-breaker knobs)."""
    from ..serve.engine import serve as _serve
    return _serve(trace, **kw)


__all__ = [
    "CORE_FIELDS", "EXTRA_BOUNDS", "FIELD_BOUNDS", "INT_FIELDS",
    "ParamLeaf", "ParamSpace", "bounds_for",
    "SPEC_VERSION", "ProxySpec", "SpecError", "validate_spec_json",
    "HadoopStack", "MPIStack", "OpenMPStack", "RunReport", "SparkStack",
    "Stack", "cache_cap", "cache_stats", "get_stack", "list_stacks",
    "register_stack", "reset_cache_stats", "tune_structure",
    "grouped_stats", "reset_grouped_stats",
    "segment_max_stats", "reset_segment_max_stats",
    "ExecutablePool", "get_pool", "pool_stats", "serve",
    "FAILURE_CLASSES", "classify_failure", "failure_is_retryable",
    "FaultPlan", "InjectedFailure", "default_fault_rate",
    "FINGERPRINT_CHANNELS", "FINGERPRINT_VERSION", "WorkloadFingerprint",
    "fingerprint", "validate_fingerprint_json",
    "SubsetReport", "normalize_fingerprints", "subset_fingerprints",
]
