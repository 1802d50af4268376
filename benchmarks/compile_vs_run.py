"""Compile-once/run-many engine benchmark: trace counts, first-call vs
steady-state time, and autotune-sweep wall time on a reference Table-3
proxy — against the pre-PR execution model (rebuild + re-jit per run,
whole-program lower+compile per tuner measurement).

Emits ``BENCH_engine.json`` at the repo root so future PRs have a perf
trajectory to regress against; also prints the harness CSV rows.

Perf gate: the run **fails (non-zero exit)** when the compile-once
contract regresses — ``population_retraces > 0`` — or when bucketed
population execution loses to the sequential per-candidate loop
(``exec_speedup_x < 1``); CI's smoke step keys off the exit code.

Two further gates ride on top:

* **bench-baseline regression** — the committed ``BENCH_engine.json`` is
  the baseline; a fresh run whose ``population_sweep.eval_speedup_x`` or
  ``exec_speedup_x`` drops more than ``REPRO_BENCH_REGRESSION_FRAC``
  (default 20%) below it fails.  ``REPRO_BENCH_BASELINE`` points at an
  alternate baseline file; an empty value disables the comparison.
* **structure_sweep** — the structural tuner must beat the weight-only
  population tuner on a fidelity target reachable only by a structure
  change, with zero engine retraces and zero new body compiles once the
  component pool is profiled.
* **serve_sweep** — the serving engine must hold the compile-once
  contract under a warmed mixed-proxy request stream
  (``steady_state_retraces == 0``, hard gate) and its micro-batch
  capacity ratio ``batch_speedup_x`` is baseline-gated like the
  population speedups (see :mod:`benchmarks.serve_bench`).
* **megakernel_sweep** — a mega-eligible fused chain must be
  bit-identical across the one-kernel Pallas lowering, the
  ``fori_loop``+``switch`` path, and the unfused plan, with zero
  retraces across dynamic weight steps; on a leg whose backends resolve
  pallas with the megakernel armed, at least one dispatch must actually
  take the one-kernel lowering (``mega_dispatches > 0``).
  ``stage_speedup_x`` is recorded, not value-gated (CPU CI runs the
  Pallas interpreter).
* **serve_faults** — resilient serving under a seeded chaos plan
  (injected executor failures + stragglers at ``REPRO_FAULT_RATE`` —
  CI's ``chaos`` leg): hard gates ``lost_requests == 0`` and
  ``steady_state_retraces == 0`` under injection, plus the seeded
  virtual-clock chaos run must be bit-reproducible.
* **ai_structure_sweep** — the structural tuner must reach an
  lm_train-style target (``ai_fidelity_harness``) by *inserting* an
  attention/recurrent dwarf component, again with zero engine traces
  and zero new body compiles warm.
* **distill_sweep** — the measurement-to-proxy loop: every
  ``PROXY_SPECS`` member's measured ``fingerprint`` must reproduce its
  hand-measured metric dict exactly, and ``StructuralTuner`` targeted at
  the fingerprint must recover a deviation ≤ the hand-declared-target
  run's with zero engine traces and zero new body compiles warm; the
  fingerprint suite then subsets (``core/subset.py``) with full
  coverage (every member within its cluster's recorded bound) and the
  compression ratio lands in the payload.
* **lm_proxy** — the LM-fleet proxy bench must produce non-zero
  accuracy rows for every active dry-run cell (a missing cell is
  regenerated at reduced scale; an unregenerable one raises), with
  ``mean_accuracy`` baseline-gated like the speedups.
"""

from __future__ import annotations

import json
import os
import time
from statistics import median
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ParamSpace, ProxySpec, cache_stats, get_stack
from repro.core import engine, schedule
from repro.core.autotune import AutoTuner, PopulationTuner
from repro.core.dag import (Edge, ProxyDAG, _accumulate, _gather_inputs,
                            _init_sources, _terminals)
from repro.core.dwarfs import ComponentParams, get_component
from repro.core.dwarfs.base import fit_buffer
from repro.core.proxy import ProxyBenchmark
from repro.core.structsearch import (StructuralTuner, ai_fidelity_harness,
                                     structural_fidelity_harness)
from repro.core.workloads import PROXY_SPECS, mega_chain

from .common import ROOT, csv_row
from .lm_proxy import lm_proxy_summary
from .serve_bench import bench_serve_faults, bench_serve_sweep

BENCH_JSON = ROOT / "BENCH_engine.json"

#: reference proxy (paper Table 3) and sweep shape
REFERENCE = "terasort"
N_STEADY = int(os.environ.get("REPRO_BENCH_STEADY_ITERS", "8"))
SWEEP_WEIGHTS = (1, 2, 4, 8, 16, 32, 64)
TUNE_ITERS = int(os.environ.get("REPRO_BENCH_TUNE_ITERS", "6"))
N_POP = int(os.environ.get("REPRO_BENCH_POPULATION", "16"))
POP_STEPS = int(os.environ.get("REPRO_BENCH_POP_STEPS", "4"))
EXEC_REPS = int(os.environ.get("REPRO_BENCH_EXEC_REPS", "3"))
#: the eval (scoring) pass is ~1ms of numpy — time EVAL_INNER passes per
#: rep and take the median of *paired* per-rep ratios over EVAL_REPS, or
#: timer noise and machine-speed drift alone trip the 20% baseline gate
EVAL_REPS = int(os.environ.get("REPRO_BENCH_EVAL_REPS", "5"))
EVAL_INNER = int(os.environ.get("REPRO_BENCH_EVAL_INNER", "8"))
STRUCT_BUDGET = int(os.environ.get("REPRO_BENCH_STRUCT_BUDGET", "96"))
#: candidate budget per distillation run (two tuner runs per proxy — the
#: hand-target run and the fingerprint-target replay — times six proxies,
#: so the default stays small)
DISTILL_BUDGET = int(os.environ.get("REPRO_BENCH_DISTILL_BUDGET", "48"))
#: clusters kept when subsetting the distilled fingerprint suite
DISTILL_CLUSTERS = int(os.environ.get("REPRO_BENCH_DISTILL_CLUSTERS", "3"))

#: >20% drop of a gated speedup vs the committed baseline fails the run
REGRESSION_FRAC = float(os.environ.get("REPRO_BENCH_REGRESSION_FRAC", "0.2"))
#: hard floor for ``exec_speedup_x``: the bucketed population path must
#: not *lose* to the sequential loop.  On a small shared host the two
#: paths are near parity (a vmapped bucket cannot out-parallelize two
#: cores) and the paired-ratio median jitters a few percent around 1.0,
#: so the floor carries a small noise margin — catastrophic losses are
#: what it exists to catch; gradual decay is the baseline gate's job
EXEC_FLOOR = float(os.environ.get("REPRO_BENCH_EXEC_FLOOR", "0.95"))
#: gated ``population_sweep`` fields (speedups are same-machine ratios, so
#: they regress meaningfully even when CI hardware differs from the
#: machine that committed the baseline)
BASELINE_GATED = ("eval_speedup_x", "exec_speedup_x")
#: gated ``serve_sweep`` fields — like the population speedups these are
#: same-machine ratios (micro-batched vs sequential makespans of one
#: paired run), comparable across runs on like hardware/backends
SERVE_GATED = ("batch_speedup_x",)


def _reference_proxy():
    return ProxySpec.from_json(PROXY_SPECS[REFERENCE]).to_benchmark()


def _seed_build(dag):
    """The seed engine's execution form, reproduced faithfully as the
    pre-PR baseline: weight repeats Python-unrolled (graph size O(sum of
    weights)), the whole fn rebuilt and re-jitted per parameter step."""
    dag.validate()
    edges = dag._rounded_edges()
    sources, sink = dict(dag.sources), dag.sink

    def run(rng):
        nodes = _init_sources(sources, rng)
        for ei, e in enumerate(edges):
            x = _gather_inputs(e, [nodes[s] for s in e.src])
            comp = get_component(e.component)
            if e.params.weight == 0:
                out = fit_buffer(x, e.params.data_size)
            else:
                out = x
                for w in range(e.params.weight):      # unrolled repeats
                    r = jax.random.fold_in(rng, 10_000 + 131 * ei + w)
                    out = comp(fit_buffer(out, e.params.data_size),
                               e.params, r)
            nodes[e.dst] = _accumulate(nodes.get(e.dst), out)
        if sink is not None:
            return jnp.sum(nodes[sink])
        return sum(jnp.sum(nodes[t]) for t in _terminals(edges))

    return run


def bench_engine_run_path() -> Dict[str, float]:
    """First call (compile) vs steady state through the executable cache."""
    stack = get_stack("openmp")
    proxy = _reference_proxy()
    rng = jax.random.PRNGKey(0)
    t0 = cache_stats()["traces"]
    first = stack.run(proxy, rng=rng).wall_s
    steady = median(stack.run(proxy, rng=rng).wall_s
                    for _ in range(N_STEADY))
    return {
        "first_call_s": first,
        "steady_state_s": steady,
        "compile_amortization_x": first / max(steady, 1e-9),
        "traces": cache_stats()["traces"] - t0,   # must be 1 (cold only)
    }


def bench_weight_sweep() -> Dict[str, float]:
    """Stepping an edge weight across the sweep: cached executable vs the
    pre-PR model (fresh ``build()`` + ``jax.jit`` per step = retrace)."""
    stack = get_stack("openmp")
    rng = jax.random.PRNGKey(0)

    proxy = _reference_proxy()
    stack.run(proxy, rng=rng)                     # warm the cache
    t0 = cache_stats()["traces"]
    t = time.perf_counter()
    for w in SWEEP_WEIGHTS:
        proxy.dag.edges[2].params.weight = w      # quick_sort edge
        stack.run(proxy, rng=rng)
    engine_s = time.perf_counter() - t
    engine_traces = cache_stats()["traces"] - t0

    pre = _reference_proxy()
    t = time.perf_counter()
    for w in SWEEP_WEIGHTS:
        pre.dag.edges[2].params.weight = w
        out = jax.jit(_seed_build(pre.dag))(rng)  # the seed's per-step path
        jax.block_until_ready(out)
    pre_pr_s = time.perf_counter() - t

    return {
        "steps": len(SWEEP_WEIGHTS),
        "engine_s": engine_s,
        "engine_retraces": engine_traces,
        "pre_pr_s": pre_pr_s,
        "speedup_x": pre_pr_s / max(engine_s, 1e-9),
    }


def bench_autotune_sweep() -> Dict[str, float]:
    """Whole autotune sweeps, engine measurement vs legacy per-step
    whole-program profiling."""
    target = engine.measure(_reference_proxy().dag)

    def _tune(measurement: str) -> float:
        tuner = AutoTuner(target, tol=0.05, max_iter=TUNE_ITERS,
                          measurement=measurement)
        proxy = _reference_proxy()
        proxy.dag.edges[2].params.weight = 1      # detuned start
        proxy.dag.edges[3].params.weight = 8
        t = time.perf_counter()
        tuner.tune(proxy)
        return time.perf_counter() - t

    engine_s = _tune("engine")
    profile_s = _tune("profile")
    return {
        "max_iter": TUNE_ITERS,
        "engine_s": engine_s,
        "profile_s": profile_s,
        "speedup_x": profile_s / max(engine_s, 1e-9),
    }


def _tuner_generation_candidates(space, base, step: int) -> "np.ndarray":
    """A tuner-generation-shaped candidate batch: multiplicative jitter of
    the dynamic leaves around the current point (what an evolution step
    actually draws), not a full log-uniform resample — execution cost of a
    vmapped batched ``while`` is ``max`` over candidates, so the candidate
    spread is part of the workload definition."""
    rs = np.random.RandomState(step)
    dyn = space.dynamic_mask()
    matrix = np.tile(base, (N_POP, 1))
    jitter = rs.uniform(0.5, 2.0, size=(N_POP, int(dyn.sum())))
    matrix[:, dyn] = np.maximum(matrix[:, dyn], 1.0) * jitter
    # clamp only the dynamic columns: static leaves define the shared
    # structure and may legitimately sit outside the nominal bounds
    matrix[:, dyn] = space.clamp(matrix)[:, dyn]
    return matrix


def bench_population_sweep() -> Dict[str, float]:
    """A population-tuner sweep over the reference proxy: per step, score
    all ``N_POP`` candidates (vectorized compositional engine) and execute
    them (one vmapped call) — against the pre-PR sequential loop
    (per-candidate clone + ``engine.measure`` + ``stack.run``).  The
    population path must compile at most as many executables as a single
    candidate and retrace zero times across the sweep."""
    stack = get_stack("openmp")
    rng = jax.random.PRNGKey(0)
    proxy = _reference_proxy()
    space = ParamSpace.from_dag(proxy.dag)
    base = space.values(proxy.dag)
    # a faithful tuner sweep: generation 0 is the log-uniform random-search
    # seed (PopulationTuner's actual first draw — the straggler-heavy batch
    # the bucket schedule exists for), later generations are evolution-step
    # jitter around the current point
    mats = [space.sample_dynamic(N_POP, base, seed=0)] + \
        [_tuner_generation_candidates(space, base, s)
         for s in range(1, POP_STEPS)]

    # executable accounting on *cold* per-instance caches: how many
    # compiles does one candidate cost vs a 16-candidate population?
    from repro.api.stack import OpenMPStack
    m0 = cache_stats()["misses"]
    OpenMPStack().run(proxy, rng=rng)
    single_compiles = cache_stats()["misses"] - m0
    m1 = cache_stats()["misses"]
    OpenMPStack().run_population(proxy, mats[0], space=space)
    population_compiles = cache_stats()["misses"] - m1

    engine.measure(proxy.dag)                   # warm the per-edge caches
    scorer = engine.PopulationScorer(proxy.dag, space)
    scorer(mats[0])                             # warm (nothing to compile)
    stack.run(proxy, rng=rng)                   # warm the shared stack
    stack.run_population(proxy, mats[0], space=space)

    # candidate-evaluation sweep (the tuner scoring hot path); both paths
    # interleaved min-of-reps — the eval pass is ~1ms, far too small for a
    # single-shot time to gate a 20% baseline regression on
    t0 = cache_stats()["traces"]
    e0 = engine.stats()

    def _eval_pop() -> float:
        t = time.perf_counter()
        for _ in range(max(EVAL_INNER, 1)):
            for m in mats:
                scorer(m)
        return (time.perf_counter() - t) / max(EVAL_INNER, 1)

    def _eval_seq() -> float:
        # the pre-PR per-candidate measure loop
        t = time.perf_counter()
        for m in mats:
            for row in m:
                trial = proxy.clone()
                space.apply(trial.dag, row)
                engine.measure(trial.dag)
        return time.perf_counter() - t

    # paired ratios: each rep measures both paths back to back, so CPU
    # frequency / neighbor drift hits numerator and denominator alike;
    # the gated speedup is the median per-rep ratio
    eval_pop_times, eval_seq_times = [], []
    for _ in range(max(EVAL_REPS, 1)):
        eval_pop_times.append(_eval_pop())
        eval_seq_times.append(_eval_seq())
    eval_pop_s, eval_seq_s = min(eval_pop_times), min(eval_seq_times)
    eval_speedup = median(s / max(p, 1e-9)
                          for p, s in zip(eval_pop_times, eval_seq_times))

    def _exec_pop() -> float:
        # bucketed execution sweep (one call per weight stratum; every
        # bucket reuses the single (plan, bucket_size) executable)
        t = time.perf_counter()
        for m in mats:
            stack.run_population(proxy, m, space=space)
        return time.perf_counter() - t

    def _exec_seq() -> float:
        # the pre-PR per-candidate evaluation loop
        t = time.perf_counter()
        for m in mats:
            for row in m:
                trial = proxy.clone()
                space.apply(trial.dag, row)
                stack.run(trial, rng=rng)
        return time.perf_counter() - t

    # interleave the passes so machine drift hits both paths alike; the
    # gated speedup is the median of *paired* per-rep ratios (robust to
    # between-rep frequency drift on a 2-core shared box), the absolute
    # times are the least-noise (min) of each path
    pop_times, seq_times = [], []
    for _ in range(max(EXEC_REPS, 1)):
        pop_times.append(_exec_pop())
        seq_times.append(_exec_seq())
    exec_pop_s, exec_seq_s = min(pop_times), min(seq_times)
    exec_speedup = median(s / max(p, 1e-9)
                          for p, s in zip(pop_times, seq_times))
    pop_retraces = cache_stats()["traces"] - t0
    pop_engine_traces = engine.stats()["traces"] - e0["traces"]

    # the pre-plan vmapped path for reference: one whole-population batch,
    # so every candidate pays the population-wide max trip count
    stack.run_population(proxy, mats[0], space=space, bucket_size=N_POP)
    t = time.perf_counter()
    for m in mats:
        stack.run_population(proxy, m, space=space, bucket_size=N_POP)
    exec_single_batch_s = time.perf_counter() - t

    # population-tuner smoke: a real (tiny) tuning run end to end
    target = engine.measure(_reference_proxy().dag)
    smoke = _reference_proxy()
    smoke.dag.edges[2].params.weight = 1
    smoke.dag.edges[3].params.weight = 8
    t = time.perf_counter()
    res = PopulationTuner(target, tol=0.10, population=8, generations=2,
                          seed=0).tune(smoke)
    tuner_smoke_s = time.perf_counter() - t

    return {
        "population": N_POP,
        "steps": POP_STEPS,
        # candidate evaluation (scoring): the >=5x tuner-throughput axis;
        # the speedups are medians of paired per-rep ratios (gate-stable)
        "eval_population_s": eval_pop_s,
        "eval_sequential_s": eval_seq_s,
        "speedup_x": eval_speedup,
        "eval_speedup_x": eval_speedup,
        # bucketed execution: per-bucket trip bounds recover the
        # sequential-sum cost model (the candidate axis still shards on a
        # mesh); exec_single_batch_s is the old whole-population vmapped
        # path whose wall-clock was max-over-candidates bound
        "exec_population_s": exec_pop_s,
        "exec_sequential_s": exec_seq_s,
        "exec_speedup_x": exec_speedup,
        "exec_single_batch_s": exec_single_batch_s,
        "bucket_speedup_x": exec_single_batch_s / max(exec_pop_s, 1e-9),
        # compile-once contract
        "executables_single_candidate": single_compiles,
        "executables_16_candidates": population_compiles,
        "population_retraces": pop_retraces,
        "population_engine_traces": pop_engine_traces,
        # end-to-end tuner smoke
        "tuner_smoke_s": tuner_smoke_s,
        "tuner_smoke_accuracy": res.final_accuracy.get("avg", 0.0),
        "tuner_smoke_candidates": res.candidates_evaluated,
    }


def bench_plan_sweep() -> Dict[str, object]:
    """ExecutionPlan lowering diagnostics: how many edges fuse per Table-3
    proxy at the live ``REPRO_FUSION_THRESHOLD``, plus the weight-bucket
    schedule of a tuner-generation candidate batch on the reference proxy
    — the per-bucket trip bounds that replace the population-wide max."""
    proxies = {}
    for name in sorted(PROXY_SPECS):
        dag = ProxySpec.from_json(PROXY_SPECS[name]).to_benchmark().dag
        rep = schedule.lower(dag).report()
        proxies[name] = {"edges": rep["edges"], "stages": rep["stages"],
                         "fused_stages": rep["fused_stages"]}
    proxy = _reference_proxy()
    space = ParamSpace.from_dag(proxy.dag)
    mat = space.sample_dynamic(N_POP, space.values(proxy.dag), seed=0)
    plan = schedule.lower(proxy.dag)
    sched = plan.bucket_schedule(
        space.stack_candidates(proxy.dag, mat))
    return {
        "fusion_threshold": schedule.fusion_threshold(),
        "reference_partition": plan.report()["partition"],
        "fused_stage_counts": proxies,
        "population": N_POP,
        "bucket_signature": list(sched.signature),
        "bucket_trip_bounds": sched.trip_bounds(),
        "bucket_valid_counts": [b.valid for b in sched.buckets],
        "bucket_masses": [float(m) for m in sched.bucket_masses()],
        "single_batch_trip_bound": max(sched.trip_bounds() or [0]),
    }


def bench_megakernel_sweep() -> Dict[str, object]:
    """Megakernel contract on a mega-eligible fused chain: engagement
    (per-trace dispatch counts from ``schedule.mega_stats()``), scalar
    parity against the ``fori_loop``+``switch`` path and the unfused
    plan, steady-state retraces across dynamic weight steps, and the
    megakernel-vs-switch stage timing ratio.

    Runs under whatever backend env the CI leg exports.  On a leg whose
    backends resolve XLA (or with ``REPRO_MEGAKERNEL=0``) the stage
    falls back and ``engaged_expected`` is False — everything is still
    recorded, but only the megakernel leg gates ``mega_dispatches > 0``.
    ``stage_speedup_x`` is recorded, never value-gated: on CPU the
    Pallas *interpreter* executes the kernel, so the ratio measures
    interpreter overhead, not accelerator wins (see ROADMAP)."""
    from repro.kernels.dispatch import megakernel_enabled, resolve_backend

    dag = mega_chain()
    fused = schedule.lower(dag, threshold=1e30, cache=False)
    unfused = schedule.lower(dag, threshold=0.0, cache=False)
    engaged_expected = (fused.mega_stage_count > 0
                        and megakernel_enabled()
                        and resolve_backend(None) == "pallas")

    space = ParamSpace.from_dag(dag)
    dyns = list(space.unstack_candidates(space.stack_candidates(
        dag, space.sample_dynamic(8, space.values(dag), seed=5))))
    rng = jax.random.PRNGKey(0)

    def jitted(plan, counter):
        pfn = plan.build_parametric()

        def counted(r, d):
            counter["n"] += 1
            return pfn(r, d)

        return jax.jit(counted)

    def steady(fn):
        fn(rng, dyns[0]).block_until_ready()     # warm
        t = time.perf_counter()
        for d in dyns:
            out = fn(rng, d)
        out.block_until_ready()
        return (time.perf_counter() - t) / len(dyns)

    schedule.reset_mega_stats()
    traces = {"n": 0}
    jmega = jitted(fused, traces)
    mega_steady_s = steady(jmega)
    mega_out = np.asarray(jmega(rng, dyns[0]))
    stats = schedule.mega_stats()
    steady_state_retraces = traces["n"] - 1      # first call is the warmup

    # the same fused plan on the fori_loop+switch path (megakernel
    # disarmed), plus the unfused per-edge plan — the parity oracles
    prev = os.environ.get("REPRO_MEGAKERNEL")
    os.environ["REPRO_MEGAKERNEL"] = "0"
    try:
        jswitch = jitted(fused, {"n": 0})
        switch_steady_s = steady(jswitch)
        switch_out = np.asarray(jswitch(rng, dyns[0]))
        unfused_out = np.asarray(jitted(unfused, {"n": 0})(rng, dyns[0]))
    finally:
        if prev is None:
            os.environ.pop("REPRO_MEGAKERNEL", None)
        else:
            os.environ["REPRO_MEGAKERNEL"] = prev

    return {
        "engaged_expected": engaged_expected,
        "mega_stages": fused.mega_stage_count,
        "partition": fused.report()["partition"],
        "mega_dispatches": stats["mega"],
        "fallback_dispatches": stats["fallback"],
        "parity_vs_switch": bool(mega_out == switch_out),
        "parity_vs_unfused": bool(mega_out == unfused_out),
        "steady_state_retraces": steady_state_retraces,
        "weight_steps": len(dyns),
        "mega_steady_s": mega_steady_s,
        "switch_steady_s": switch_steady_s,
        "stage_speedup_x": (switch_steady_s / mega_steady_s
                            if mega_steady_s > 0 else 0.0),
    }


def bench_structure_sweep() -> Dict[str, float]:
    """Structural vs weight-only tuning under one fixed candidate budget,
    on a fidelity target reachable **only** by a structure change: the
    reference pipeline carries an fft stage the detuned seed structure
    lacks entirely, so no re-weighting of the seed's edges can create the
    missing transform channel — the weight-only tuner saturates while the
    structural tuner must insert the edge and converge.  The harness
    (DAGs + component pool) is the one definition shared with
    ``tests/test_fidelity.py`` — ``structural_fidelity_harness`` — so the
    gate and the tier-1 test verify the same contract.  The whole search
    scores through the compositional engine: after the component pool is
    profiled (the warmup dag), structure scoring triggers zero executable
    traces and zero new body compiles."""
    reference, detuned, pool = structural_fidelity_harness()
    size = reference.sources["records"]
    chunk = reference.edges[0].params.chunk_size

    # profile every pool component at the mutation-site shape (extras-free
    # edges, exactly what machine-inserted edges carry) so the search
    # itself compiles nothing
    warmup = ProxyDAG(
        "struct_warmup", {"records": size},
        [Edge(c, ["records"] if i == 0 else [f"w{i - 1}"], f"w{i}",
              ComponentParams(data_size=size, chunk_size=chunk))
         for i, c in enumerate(pool)], f"w{len(pool) - 1}")
    engine.measure(warmup)
    target = engine.measure(reference)

    budget = STRUCT_BUDGET
    t = time.perf_counter()
    weight_only = PopulationTuner(
        target, tol=0.10, population=16,
        generations=max(2, budget // 16), max_candidates=budget,
        seed=0, execute=False).tune(ProxyBenchmark(detuned))
    weight_only_s = time.perf_counter() - t

    e0 = engine.stats()
    t = time.perf_counter()
    structural = StructuralTuner(
        target, tol=0.10, max_candidates=budget, generations=4,
        components=pool, seed=0).tune(ProxyBenchmark(detuned))
    structural_s = time.perf_counter() - t
    e1 = engine.stats()

    return {
        "budget": budget,
        "weight_only_deviation": weight_only.final_deviation,
        "weight_only_candidates": weight_only.candidates_evaluated,
        "weight_only_s": weight_only_s,
        "structural_deviation": structural.final_deviation,
        "structural_converged": float(structural.converged),
        "structures_scored": structural.structures_scored,
        "weight_candidates": structural.weight_candidates,
        "structural_candidates": structural.candidates_evaluated,
        "structural_s": structural_s,
        "structural_generations": structural.generations,
        "best_lineage": structural.best_lineage,
        # the cheap-scoring contract
        "structure_engine_traces": e1["traces"] - e0["traces"],
        "structure_new_body_compiles": structural.new_body_compiles,
    }


def bench_distill_sweep() -> Dict[str, object]:
    """The measurement-to-proxy distillation contract, per proxy:

    1. **Fingerprint fidelity** — ``fingerprint(dag).metrics()`` must
       equal the engine's measured metric dict *exactly* (the channel
       basis is lossless by construction; this gate keeps it so).
    2. **Distilled ≥ hand** — a ``StructuralTuner`` run targeting the
       measured fingerprint must recover a channel deviation no worse
       than the identically-budgeted run targeting the hand-declared
       metric dict, on a detuned (all-weights-1) seed of the same
       structure.
    3. **Zero-cost warm** — the fingerprint-target run replays the same
       deterministic search, so it must hit the process-wide body cache:
       0 engine traces, 0 new body compiles.

    The distilled fingerprint suite then subsets
    (:func:`repro.core.subset.subset_fingerprints`,
    ``DISTILL_CLUSTERS`` representatives): full coverage — every member
    within its cluster's recorded bound — and the compression ratio land
    in the payload."""
    from repro.core.engine import fingerprint
    from repro.core.subset import subset_fingerprints
    from repro.core.workloads import seed_components

    pool = seed_components()
    per: Dict[str, Dict[str, float]] = {}
    fps = []
    kw = dict(tol=0.10, max_candidates=DISTILL_BUDGET, generations=2,
              structure_population=4, mutations_per_parent=2,
              components=pool, seed=0)

    def _detuned(spec):
        bench = spec.to_benchmark()
        for e in bench.dag.edges:
            e.params.extra["weight"] = 1.0
        return bench

    t_total = time.perf_counter()
    for name in sorted(PROXY_SPECS):
        spec = ProxySpec.from_json(PROXY_SPECS[name])
        dag = spec.to_dag()
        hand = engine.measure(dag)               # also warms the bodies
        fp = fingerprint(dag, name=name)
        exact = fp.metrics() == hand
        fps.append(fp)
        hand_res = StructuralTuner(hand, **kw).tune(_detuned(spec))
        e0 = engine.stats()
        fp_res = StructuralTuner(fp, **kw).tune(_detuned(spec))
        e1 = engine.stats()
        per[name] = {
            "fingerprint_exact": float(exact),
            "hand_deviation": hand_res.final_deviation,
            "distilled_deviation": fp_res.final_deviation,
            "engine_traces": e1["traces"] - e0["traces"],
            "new_body_compiles": fp_res.new_body_compiles,
        }
    wall = time.perf_counter() - t_total

    subset = subset_fingerprints(fps, k=min(DISTILL_CLUSTERS, len(fps)),
                                 seed=0)
    full_coverage = all(
        subset.distances[m] <= subset.max_distance[rep] + 1e-12
        for rep, members in subset.clusters.items() for m in members)
    return {
        "budget": DISTILL_BUDGET,
        "proxies": per,
        "wall_s": wall,
        "subset": subset.to_json(),
        "compression_x": subset.compression_x,
        "coverage": subset.coverage,
        "full_coverage": float(full_coverage),
        "representatives": subset.representatives,
    }


def bench_ai_structure_sweep() -> Dict[str, object]:
    """The AI-dwarf structural contract (``ai_fidelity_harness``, shared
    with ``tests/test_ai_dwarfs.py``): an lm_train-style reference whose
    attention stage the detuned seed lacks entirely.  No re-weighting of
    the seed's GEMM edges can create the missing ``mix_attention`` channel
    (exp-gated contractions — see :class:`repro.core.metrics.CostReport`),
    so the tuner must *insert* an attention-class component; and it must do
    so entirely through the compositional engine — zero executable traces
    and zero new body compiles once the pool is profiled."""
    reference, detuned, pool = ai_fidelity_harness()
    size = reference.sources["tokens"]
    chunk = reference.edges[0].params.chunk_size

    warmup = ProxyDAG(
        "ai_struct_warmup", {"tokens": size},
        [Edge(c, ["tokens"] if i == 0 else [f"w{i - 1}"], f"w{i}",
              ComponentParams(data_size=size, chunk_size=chunk))
         for i, c in enumerate(pool)], f"w{len(pool) - 1}")
    engine.measure(warmup)
    target = engine.measure(reference)
    from repro.core.autotune import _deviations
    seed_dev = max((abs(d) for d in _deviations(
        target, engine.measure(detuned),
        [k for k in target if abs(target[k]) > 1e-12]).values()),
        default=float("inf"))

    e0 = engine.stats()
    t = time.perf_counter()
    res = StructuralTuner(target, tol=0.10, max_candidates=STRUCT_BUDGET,
                          generations=4, components=pool,
                          seed=0).tune(ProxyBenchmark(detuned))
    wall = time.perf_counter() - t
    e1 = engine.stats()

    from repro.core.dwarfs import REGISTRY
    ai_names = {n for n, c in REGISTRY.items()
                if c.dwarf in ("attention", "gemm", "recurrent")}
    # components only a structural insertion can contribute: the seed
    # already carries gemm_train edges, so the gate keys on the
    # attention/recurrent classes (the exp-gated ones)
    attn_names = {n for n, c in REGISTRY.items()
                  if c.dwarf in ("attention", "recurrent")}
    used = {e.component for e in res.proxy.dag.edges}
    return {
        "budget": STRUCT_BUDGET,
        "deviation": res.final_deviation,
        "seed_deviation": seed_dev,
        "converged": float(res.converged),
        "structures_scored": res.structures_scored,
        "weight_candidates": res.weight_candidates,
        "ai_components_used": sorted(used & ai_names),
        "attention_class_used": sorted(used & attn_names),
        "best_lineage": res.best_lineage,
        "wall_s": wall,
        "engine_traces": e1["traces"] - e0["traces"],
        "new_body_compiles": res.new_body_compiles,
    }


def _resolved_backend() -> str:
    """The kernel backend this run measures under — part of the baseline
    identity: interpret-mode Pallas shifts absolute per-candidate costs,
    so cross-backend speedup comparisons are not regressions."""
    from repro.kernels.dispatch import default_interpret, resolve_backend
    backend = resolve_backend(None)
    if backend == "pallas" and default_interpret():
        return "pallas-interpret"
    return backend


def _load_baseline() -> Dict:
    """The **committed** ``BENCH_engine.json`` (or
    ``REPRO_BENCH_BASELINE``; empty override disables).  Read from git
    HEAD so repeated local runs — which overwrite the on-disk file — keep
    gating against the committed numbers instead of self-ratcheting on
    their own last (possibly lucky) measurement; falls back to the
    on-disk file outside a git checkout (CI checkouts are identical)."""
    path_env = os.environ.get("REPRO_BENCH_BASELINE")
    if path_env is not None and path_env.strip() == "":
        return {}
    if path_env:
        # an explicitly named baseline must load — a typo'd path silently
        # disabling the gate is exactly the rot the gate exists to stop
        with open(path_env) as f:
            return json.load(f)
    import subprocess
    try:
        committed = subprocess.run(
            ["git", "show", f"HEAD:{BENCH_JSON.name}"], cwd=str(ROOT),
            capture_output=True, text=True, timeout=30)
        if committed.returncode == 0:
            return json.loads(committed.stdout)
    except (OSError, ValueError, subprocess.SubprocessError):
        pass
    try:
        with open(BENCH_JSON) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _baseline_regressions(population: Dict[str, float],
                          baseline: Dict) -> List[str]:
    """>REGRESSION_FRAC drops of the gated population-sweep speedups vs
    the committed baseline.  Skipped when the baseline was measured under
    a different kernel backend (e.g. the pallas-interpret CI leg vs an
    XLA-measured baseline): the ratios are only comparable like-for-like,
    and the hard ``exec_speedup_x >= 1`` floor still applies everywhere."""
    base_backend = baseline.get("kernel_backend", "xla")
    if baseline and base_backend != _resolved_backend():
        return []
    base_pop = baseline.get("population_sweep", {})
    failures = []
    for key in BASELINE_GATED:
        base = base_pop.get(key)
        if base is None and key == "eval_speedup_x":
            base = base_pop.get("speedup_x")     # pre-alias baselines
        new = population.get(key)
        if not base or base <= 0 or new is None:
            continue
        if new < base * (1.0 - REGRESSION_FRAC):
            failures.append(
                f"population_sweep.{key}={new:.2f} regressed "
                f">{REGRESSION_FRAC:.0%} vs committed baseline {base:.2f}")
    return failures


def _serve_baseline_regressions(serve: Dict[str, object],
                                baseline: Dict) -> List[str]:
    """>REGRESSION_FRAC drops of the gated serve-sweep ratios vs the
    committed baseline, with the same cross-backend skip as the
    population gate (the hard ``steady_state_retraces == 0`` floor still
    applies everywhere).  Also skipped when the workload *shape* differs
    (request count / batching knobs): the capacity ratio depends on how
    full the micro-batch chunks run — a 12-request CI leg is not
    comparable to a 24-request committed baseline."""
    base_backend = baseline.get("kernel_backend", "xla")
    if baseline and base_backend != _resolved_backend():
        return []
    base_serve = baseline.get("serve_sweep", {})
    shape_keys = ("requests", "max_batch", "bucket_size", "rate_rps", "mix")
    if base_serve and any(base_serve.get(k) != serve.get(k)
                          for k in shape_keys):
        return []
    failures = []
    for key in SERVE_GATED:
        base, new = base_serve.get(key), serve.get(key)
        if not base or base <= 0 or new is None:
            continue
        if new < base * (1.0 - REGRESSION_FRAC):
            failures.append(
                f"serve_sweep.{key}={new:.2f} regressed "
                f">{REGRESSION_FRAC:.0%} vs committed baseline {base:.2f}")
    return failures


def _lm_baseline_regressions(lm: Dict[str, object],
                             baseline: Dict) -> List[str]:
    """>REGRESSION_FRAC drop of lm_proxy ``mean_accuracy`` vs the committed
    baseline.  Like the other baseline gates this only compares
    like-for-like: same kernel backend and the same cell set — including
    each cell's reduced-ness, since a reduced (CPU-smoke) cell and a full
    512-chip cell are different targets.  The hard per-cell ``acc > 0``
    floor applies everywhere regardless."""
    base_backend = baseline.get("kernel_backend", "xla")
    if baseline and base_backend != _resolved_backend():
        return []
    base_lm = baseline.get("lm_proxy", {})
    ident = [(c["name"], bool(c.get("reduced"))) for c in lm["cells"]]
    base_ident = [(c["name"], bool(c.get("reduced")))
                  for c in base_lm.get("cells", [])]
    if not base_ident or ident != base_ident:
        return []
    base, new = base_lm.get("mean_accuracy"), lm.get("mean_accuracy")
    if base and base > 0 and new is not None and \
            new < base * (1.0 - REGRESSION_FRAC):
        return [f"lm_proxy.mean_accuracy={new:.3f} regressed "
                f">{REGRESSION_FRAC:.0%} vs committed baseline {base:.3f}"]
    return []


class BenchGateError(RuntimeError):
    """A perf-contract regression the harness must not let rot silently."""


def bench_compile_vs_run() -> List[str]:
    baseline = _load_baseline()    # before this run overwrites the file
    run_path = bench_engine_run_path()
    sweep = bench_weight_sweep()
    tune = bench_autotune_sweep()
    population = bench_population_sweep()
    plan_sweep = bench_plan_sweep()
    mega = bench_megakernel_sweep()
    structure = bench_structure_sweep()
    ai_structure = bench_ai_structure_sweep()
    distill = bench_distill_sweep()
    serve = bench_serve_sweep()
    serve_faults = bench_serve_faults()
    # raises LmProxyError on a missing/unparseable dry-run cell — a dead
    # bench is a harness failure, not a quiet 0.0 csv row
    lm = lm_proxy_summary()
    failures = []
    if serve["steady_state_retraces"] > 0:
        failures.append(
            f"steady_state_retraces={serve['steady_state_retraces']} "
            f"(serving compile-once contract broken: a warmed request "
            f"stream retraced)")
    failures += _serve_baseline_regressions(serve, baseline)
    if serve_faults["lost_requests"] > 0:
        failures.append(
            f"serve_faults.lost_requests={serve_faults['lost_requests']} "
            f"(a request vanished under injected failures — the zero-loss "
            f"invariant is broken)")
    if serve_faults["steady_state_retraces"] > 0:
        failures.append(
            f"serve_faults.steady_state_retraces="
            f"{serve_faults['steady_state_retraces']} (injected failures "
            f"and stragglers must recover without retracing)")
    if not serve_faults["virtual_chaos_deterministic"]:
        failures.append(
            "serve_faults.virtual_chaos_deterministic=False (the same "
            "seeded FaultPlan produced two different virtual-clock "
            "reports)")
    if population["population_retraces"] > 0:
        failures.append(
            f"population_retraces={population['population_retraces']:.0f} "
            f"(compile-once contract broken)")
    if population["exec_speedup_x"] < EXEC_FLOOR:
        failures.append(
            f"exec_speedup_x={population['exec_speedup_x']:.2f} < "
            f"{EXEC_FLOOR:g} (bucketed population execution lost to the "
            f"sequential loop)")
    failures += _baseline_regressions(population, baseline)
    if mega["engaged_expected"] and mega["mega_dispatches"] < 1:
        failures.append(
            f"megakernel_sweep.mega_dispatches="
            f"{mega['mega_dispatches']} (backends resolve pallas and the "
            f"megakernel is armed, but no fused stage took the one-kernel "
            f"lowering)")
    if not (mega["parity_vs_switch"] and mega["parity_vs_unfused"]):
        failures.append(
            f"megakernel_sweep parity broken (vs_switch="
            f"{mega['parity_vs_switch']}, vs_unfused="
            f"{mega['parity_vs_unfused']}): the megakernel lowering is "
            f"not bit-identical to the fori_loop+switch path")
    if mega["steady_state_retraces"] > 0:
        failures.append(
            f"megakernel_sweep.steady_state_retraces="
            f"{mega['steady_state_retraces']} (dynamic weight steps "
            f"retraced a warmed megakernel executable)")
    if (structure["structural_deviation"]
            >= structure["weight_only_deviation"]):
        failures.append(
            f"structural_deviation={structure['structural_deviation']:.3f} "
            f">= weight_only {structure['weight_only_deviation']:.3f} "
            f"(structure search no longer beats weight-only tuning)")
    if structure["structure_engine_traces"] > 0:
        failures.append(
            f"structure_engine_traces="
            f"{structure['structure_engine_traces']:.0f} (structure "
            f"scoring executed the proxy)")
    if structure["structure_new_body_compiles"] > 0:
        failures.append(
            f"structure_new_body_compiles="
            f"{structure['structure_new_body_compiles']:.0f} (mutated "
            f"plans recompiled already-profiled components)")
    if ai_structure["deviation"] >= ai_structure["seed_deviation"]:
        failures.append(
            f"ai_structure.deviation={ai_structure['deviation']:.3f} >= "
            f"seed {ai_structure['seed_deviation']:.3f} (structure search "
            f"did not improve on the attention-free seed)")
    if not ai_structure["attention_class_used"]:
        failures.append(
            "ai_structure.attention_class_used is empty (the structural "
            "tuner reached an lm_train-style target without inserting any "
            "attention/recurrent dwarf component)")
    if ai_structure["engine_traces"] > 0:
        failures.append(
            f"ai_structure.engine_traces="
            f"{ai_structure['engine_traces']:.0f} (AI structure scoring "
            f"executed the proxy)")
    if ai_structure["new_body_compiles"] > 0:
        failures.append(
            f"ai_structure.new_body_compiles="
            f"{ai_structure['new_body_compiles']:.0f} (mutated plans "
            f"recompiled already-profiled AI components)")
    for name, row in sorted(distill["proxies"].items()):
        if not row["fingerprint_exact"]:
            failures.append(
                f"distill_sweep.{name}.fingerprint_exact=False (the "
                f"channel-basis fingerprint no longer reproduces the "
                f"measured metric dict — the basis went lossy)")
        if row["distilled_deviation"] > row["hand_deviation"] + 1e-9:
            failures.append(
                f"distill_sweep.{name}.distilled_deviation="
                f"{row['distilled_deviation']:.4f} > hand-target "
                f"{row['hand_deviation']:.4f} (tuning against the "
                f"measured fingerprint lost to the hand-declared target)")
        if row["engine_traces"] > 0:
            failures.append(
                f"distill_sweep.{name}.engine_traces="
                f"{row['engine_traces']:.0f} (fingerprint-target tuning "
                f"executed the proxy)")
        if row["new_body_compiles"] > 0:
            failures.append(
                f"distill_sweep.{name}.new_body_compiles="
                f"{row['new_body_compiles']:.0f} (fingerprint-target "
                f"tuning recompiled already-profiled components)")
    if not distill["full_coverage"]:
        failures.append(
            "distill_sweep.full_coverage=False (a fingerprint fell "
            "outside its cluster's recorded coverage bound)")
    for c in lm["cells"]:
        if c["acc"] <= 0:
            failures.append(
                f"lm_proxy cell {c['name']} accuracy == 0 "
                f"(dead bench row)")
    failures += _lm_baseline_regressions(lm, baseline)
    payload = {
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "kernel_backend": _resolved_backend(),
        "reference_proxy": REFERENCE,
        "run_path": run_path,
        "weight_sweep": sweep,
        "autotune_sweep": tune,
        "population_sweep": population,
        "plan_sweep": plan_sweep,
        "megakernel_sweep": mega,
        "structure_sweep": structure,
        "ai_structure_sweep": ai_structure,
        "distill_sweep": distill,
        "serve_sweep": serve,
        "serve_faults": serve_faults,
        "lm_proxy": lm,
        "gate_failures": failures,
        "engine_stats": engine.stats(),
        "stack_cache_stats": cache_stats(),
    }
    BENCH_JSON.write_text(json.dumps(payload, indent=1) + "\n")
    rows = _csv_rows(run_path, sweep, tune, population, plan_sweep, mega,
                     structure, ai_structure, distill, serve, serve_faults,
                     lm)
    if failures:
        for row in rows:           # the evidence still lands on failure
            print(row, flush=True)
        raise BenchGateError("; ".join(failures))
    return rows


def _csv_rows(run_path, sweep, tune, population, plan_sweep, mega,
              structure, ai_structure, distill, serve, serve_faults,
              lm) -> List[str]:
    return [
        csv_row("engine/run_path", run_path["steady_state_s"] * 1e6,
                f"first_s={run_path['first_call_s']:.3f};"
                f"steady_s={run_path['steady_state_s']:.4f};"
                f"amortization={run_path['compile_amortization_x']:.0f}x;"
                f"traces={run_path['traces']:.0f}"),
        csv_row("engine/weight_sweep", sweep["engine_s"] * 1e6,
                f"engine_s={sweep['engine_s']:.3f};"
                f"pre_pr_s={sweep['pre_pr_s']:.3f};"
                f"speedup={sweep['speedup_x']:.1f}x;"
                f"retraces={sweep['engine_retraces']:.0f}"),
        csv_row("engine/autotune_sweep", tune["engine_s"] * 1e6,
                f"engine_s={tune['engine_s']:.3f};"
                f"profile_s={tune['profile_s']:.3f};"
                f"speedup={tune['speedup_x']:.1f}x"),
        csv_row("engine/population_sweep", population["eval_population_s"] * 1e6,
                f"eval_speedup={population['speedup_x']:.1f}x;"
                f"exec_speedup={population['exec_speedup_x']:.1f}x;"
                f"bucket_speedup={population['bucket_speedup_x']:.1f}x;"
                f"retraces={population['population_retraces']:.0f};"
                f"executables_16={population['executables_16_candidates']:.0f};"
                f"tuner_smoke_s={population['tuner_smoke_s']:.2f}"),
        csv_row("engine/plan_sweep", 0.0,
                f"threshold={plan_sweep['fusion_threshold']:g};"
                f"ref_stages={len(plan_sweep['reference_partition'])};"
                f"buckets={plan_sweep['bucket_signature']};"
                f"trip_bounds={plan_sweep['bucket_trip_bounds']};"
                f"single_batch_trips={plan_sweep['single_batch_trip_bound']}"),
        csv_row("engine/megakernel_sweep", mega["mega_steady_s"] * 1e6,
                f"engaged={mega['engaged_expected']};"
                f"mega_dispatches={mega['mega_dispatches']};"
                f"fallbacks={mega['fallback_dispatches']};"
                f"stage_speedup={mega['stage_speedup_x']:.2f}x;"
                f"parity={mega['parity_vs_switch'] and mega['parity_vs_unfused']};"
                f"retraces={mega['steady_state_retraces']}"),
        csv_row("engine/structure_sweep", structure["structural_s"] * 1e6,
                f"structural_dev={structure['structural_deviation']:.3f};"
                f"weight_only_dev={structure['weight_only_deviation']:.3f};"
                f"budget={structure['budget']:.0f};"
                f"structures={structure['structures_scored']:.0f};"
                f"engine_traces={structure['structure_engine_traces']:.0f};"
                f"new_compiles="
                f"{structure['structure_new_body_compiles']:.0f}"),
        csv_row("engine/ai_structure", ai_structure["wall_s"] * 1e6,
                f"deviation={ai_structure['deviation']:.3f};"
                f"converged={ai_structure['converged']:.0f};"
                f"ai_used={'+'.join(ai_structure['ai_components_used'])};"
                f"attention_class="
                f"{'+'.join(ai_structure['attention_class_used'])};"
                f"engine_traces={ai_structure['engine_traces']:.0f};"
                f"new_compiles={ai_structure['new_body_compiles']:.0f}"),
        csv_row("engine/distill_sweep", distill["wall_s"] * 1e6,
                f"proxies={len(distill['proxies'])};"
                f"max_distilled_dev="
                f"{max(r['distilled_deviation'] for r in distill['proxies'].values()):.3f};"
                f"traces={sum(r['engine_traces'] for r in distill['proxies'].values()):.0f};"
                f"new_compiles="
                f"{sum(r['new_body_compiles'] for r in distill['proxies'].values()):.0f};"
                f"compression={distill['compression_x']:.1f}x;"
                f"coverage={distill['coverage']:.2f};"
                f"reps={'+'.join(distill['representatives'])}"),
        csv_row("engine/lm_proxy", lm["mean_accuracy"] * 100,
                f"cells={lm['n_cells']};"
                f"mean_acc={lm['mean_accuracy']:.3f};"
                f"min_acc={lm['min_accuracy']:.3f};"
                f"reduced={lm['n_reduced']}"),
        csv_row("engine/serve_sweep", serve["latency_p95_s"] * 1e6,
                f"p50_s={serve['latency_p50_s']:.4f};"
                f"p95_s={serve['latency_p95_s']:.4f};"
                f"p99_s={serve['latency_p99_s']:.4f};"
                f"throughput_rps={serve['throughput_rps']:.2f};"
                f"ttfr_s={serve['time_to_first_result_s']:.4f};"
                f"batch_speedup={serve['batch_speedup_x']:.2f}x;"
                f"retraces={serve['steady_state_retraces']};"
                f"warmup_compiles={serve['warmup_compiles']}"),
        csv_row("engine/serve_faults",
                serve_faults["chaos_latency_p99_s"] * 1e6,
                f"fault_rate={serve_faults['fault_rate']:g};"
                f"lost={serve_faults['lost_requests']};"
                f"failures={serve_faults['failures']};"
                f"retries={serve_faults['retries']};"
                f"retraces={serve_faults['steady_state_retraces']};"
                f"flush_p99_win="
                f"{serve_faults['flush_p99_improvement_x']:.2f}x;"
                f"deterministic="
                f"{serve_faults['virtual_chaos_deterministic']}"),
    ]


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for row in bench_compile_vs_run():
        print(row)
    print(f"wrote {BENCH_JSON}")
