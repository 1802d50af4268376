#!/usr/bin/env python3
"""Drive the proxy pipeline once, end to end, on one TPU chip.

One process holds the chip and runs six phases through the entry points a
user calls:

1. ``device``     — platform, device kind and count, the resolved kernel
                    backend and interpret flag, the compile-cache directory.
                    Off the chip the run stops here.
2. ``originals``  — the four BigDataBench originals at ``SCALES["full"]``,
                    each checked against a property that is plain to verify.
3. ``proxies``    — every ``PROXY_SPECS`` proxy on its own stack and on
                    ``openmp``, against the same run with the stock XLA
                    lowering forced; the kernels' compiled text; a fused
                    stage through the megakernel; the sort network and the
                    megakernel against plain XLA on whole buffers.
4. ``population`` — a 16-candidate population of the TeraSort proxy at
                    1, 4 and 16 candidates per bucket, against the
                    sequential ``run`` loop.
5. ``serve``      — a seeded 64-request Poisson trace over the serving mix,
                    with no failure, retry, degradation or retrace.
6. ``tune``       — two ``PopulationTuner`` generations against the
                    fingerprint of the TeraSort original.

The last line of standard output is one JSON object; it reads
``{"ok": true, ...}`` only when every phase passed on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the mesh phase, on four chips
    python chip_smoke.py --tiny       # CPU rehearsal at small sizes
                                      # (Pallas under the interpreter)

``--chips 4`` runs ``MPIStack`` and ``SparkStack`` ``run_batch``,
``run_population`` and ``serve`` on a 4-device mesh and compares them bit
for bit with ``openmp`` on one device.  On the CPU it rehearses with
``XLA_FLAGS=--xla_force_host_platform_device_count=4 ... --chips 4 --tiny``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback
from typing import Any, Callable, Dict, Iterator, List, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import ParamSpace, ProxySpec, get_stack, serve  # noqa: E402
from repro.core import schedule  # noqa: E402
from repro.core.autotune import PopulationTuner  # noqa: E402
from repro.core.dag import ProxyDAG  # noqa: E402
from repro.core.dwarfs import get_component  # noqa: E402
from repro.core.workloads import (PROXY_SPECS, SCALES,  # noqa: E402
                                  mega_chain, workload_fingerprint,
                                  workload_step_fn)
from repro.kernels.dispatch import (default_interpret,  # noqa: E402
                                    forced_backend, resolve_backend)
from repro.serve.engine import poisson_trace  # noqa: E402

from benchmarks.serve_bench import SERVE_MIX  # noqa: E402

#: sizes per mode: the chip runs the originals at their full scale and the
#: issue's population/trace sizes; the CPU rehearsal shrinks every count
SIZES = {
    "chip": dict(scale="full", population=16, requests=64, tuner_pop=16,
                 batch=8, lanes=4),
    "tiny": dict(scale="tiny", population=4, requests=8, tuner_pop=4,
                 batch=4, lanes=1),
}

class SmokeFailure(AssertionError):
    """A check of the smoke run did not hold."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _host(x: Any) -> np.ndarray:
    return np.asarray(jax.device_get(x))


def _max_diff(a: Any, b: Any) -> float:
    return float(np.max(np.abs(np.asarray(_host(a), np.float64)
                               - np.asarray(_host(b), np.float64))))


def _same(a: Any, b: Any, tol: Optional[float] = None) -> bool:
    """Bit-identical, or within ``tol`` (relative and absolute)."""
    a, b = _host(a), _host(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    if tol is None:
        return a.tobytes() == b.tobytes()
    return bool(np.allclose(a, b, rtol=tol, atol=tol))


def _parity_tol(dag: ProxyDAG) -> Optional[float]:
    """The loosest ``parity_tol`` among the DAG's components, ``None``
    when every component must match bit for bit."""
    tols = [get_component(e.component).parity_tol for e in dag.edges]
    tols = [t for t in tols if t is not None]
    return max(tols) if tols else None


def _spec(name: str) -> ProxySpec:
    return ProxySpec.from_json(PROXY_SPECS[name])


@contextlib.contextmanager
def _env(**kv: Optional[str]) -> Iterator[None]:
    """Set (or, with ``None``, unset) environment variables for a block."""
    prev = {k: os.environ.get(k) for k in kv}
    try:
        for k, v in kv.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        yield
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _device() -> Dict[str, Any]:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def _dir_bytes(path: Optional[str]) -> int:
    """Total size of the files under ``path`` (0 when it does not exist)."""
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, files in os.walk(path or "") for f in files)


def phase_device(cfg: Dict[str, Any]) -> Dict[str, Any]:
    dev = _device()
    cache = jax.config.jax_compilation_cache_dir
    info = {**dev, "backend": resolve_backend(),
            "interpret": default_interpret(),
            "compile_cache_dir": cache,
            "compile_cache_bytes_at_start": _dir_bytes(cache)}
    if cfg["tiny"]:
        return info
    _require(dev["platform"] == "tpu",
             f"no TPU: JAX found platform {dev['platform']!r}")
    _require(dev["count"] == cfg["chips"],
             f"expected {cfg['chips']} chip(s), JAX sees {dev['count']}")
    _require(info["backend"] == "pallas",
             f"kernel backend resolved to {info['backend']!r}, not pallas")
    _require(not info["interpret"], "Pallas kernels would run interpreted")
    return info


def phase_originals(cfg: Dict[str, Any]) -> Dict[str, Any]:
    scale = cfg["scale"]
    s = SCALES[scale]
    expected = {
        "kmeans": [(s["kmeans_k"], s["kmeans_d"]), (s["kmeans_iters"],)],
        "pagerank": [(s["pagerank_v"],), (16,), (s["pagerank_iters"],)],
        "sift": [(s["sift_b"], 64), (8,), (), (s["sift_b"], 32)],
    }
    info: Dict[str, Any] = {"scale": scale}
    for name in ("terasort", "kmeans", "pagerank", "sift"):
        fn, args = workload_step_fn(name, scale)
        t0 = time.perf_counter()
        out = jax.block_until_ready(jax.jit(fn)(*args))
        info[f"{name}_s"] = time.perf_counter() - t0
        if name == "terasort":
            n = s["terasort_n"]
            keys, payload, counts = (_host(o) for o in out)
            in_keys, in_payload = (_host(a) for a in args)
            _require(keys.shape == (n,) and payload.shape == (n,),
                     f"terasort output shapes {keys.shape}, {payload.shape}")
            _require(bool(np.all(keys[1:] >= keys[:-1])),
                     "terasort keys are not sorted")
            _require(np.array_equal(keys, np.sort(in_keys))
                     and np.array_equal(np.sort(payload), np.sort(in_payload)),
                     "terasort output is not a permutation of its input")
            _require(int(counts.sum()) == n,
                     f"terasort partition counts sum to {int(counts.sum())}")
            info["terasort_records"] = n
            continue
        leaves = jax.tree_util.tree_leaves(out)
        shapes = [tuple(np.shape(x)) for x in leaves]
        _require(shapes == expected[name],
                 f"{name} output shapes {shapes}, expected {expected[name]}")
        _require(all(np.isfinite(_host(x)).all() for x in leaves),
                 f"{name} output is not finite")
    return info


def _kernel_checks() -> Dict[str, Any]:
    """The sort network and the megakernel against plain XLA, on whole
    buffers rather than the proxies' scalar sums."""
    from repro.kernels.megakernel import mega_body, mega_lane, mega_stage_kernel
    from repro.kernels.sort_net.ops import sort_rows
    interp = default_interpret()
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 2048), jnp.float32)
    _require(_same(sort_rows(x, interpret=interp), jnp.sort(x, axis=1)),
             "sort_net rows differ from jnp.sort")

    dag = mega_chain()
    members = [(e.component, e.params) for e in dag.edges]
    lane = mega_lane(members)
    bodies = [mega_body(c, p) for c, p in members]
    weights = jnp.asarray([int(p.weight) for _, p in members], jnp.int32)
    flat = jax.random.uniform(jax.random.PRNGKey(2),
                              (dag.sources["src"],), jnp.float32)
    got = jax.jit(lambda v, w: mega_stage_kernel(
        v, w, bodies, lane, interpret=interp))(flat, weights)

    @jax.jit
    def ref(v):
        v = v.reshape(-1, lane)
        for body, (_, p) in zip(bodies, members):
            for _ in range(int(p.weight)):
                v = body(v)
        return v.reshape(-1)

    _require(_same(got, ref(flat)),
             "megakernel buffer differs from its bodies run by XLA")
    return {"kernel_checks": "sort_net, megakernel"}


def phase_proxies(cfg: Dict[str, Any]) -> Dict[str, Any]:
    info: Dict[str, Any] = {}
    problems: List[str] = []
    rng = jax.random.PRNGKey(0)
    for name in sorted(PROXY_SPECS):
        spec = _spec(name)
        dag = spec.to_dag()
        tol = _parity_tol(dag)
        for stack in dict.fromkeys((spec.stack, "openmp")):
            t0 = time.perf_counter()
            got = get_stack(stack).run(spec).result
            dt = time.perf_counter() - t0
            with forced_backend("xla"):
                ref = get_stack(stack).run(spec).result
            info[f"{name}/{stack}_s"] = dt
            info[f"{name}/{stack}_vs_xla"] = (
                "bit-identical" if _same(got, ref) else _max_diff(got, ref))
            if not _host(got).size or not np.isfinite(_host(got)).all():
                problems.append(f"{name} on {stack}: result {_host(got)}")
            elif not _same(got, ref, tol):
                problems.append(
                    f"{name} on {stack}: pallas {_host(got)!r} != xla "
                    f"{_host(ref)!r} (tol {tol})")
        # the program the openmp stack ran: its kernels compiled, not
        # interpreted
        text = jax.jit(schedule.lower(dag).build_parametric()).lower(
            rng, dag.dynamic_params()).compile().as_text()
        calls = text.count("tpu_custom_call")
        info[f"{name}/tpu_custom_calls"] = calls
        if not cfg["tiny"] and calls == 0:
            problems.append(f"{name}: no tpu_custom_call in compiled text")

    chain = mega_chain()
    plan = schedule.lower(chain)
    _require(plan.mega_stage_count > 0,
             f"the smoke chain lowered to no mega stage: "
             f"{plan.report()['partition']}")
    schedule.reset_mega_stats()
    got = get_stack("openmp").run(chain).result
    stats = schedule.mega_stats()
    info["mega_stats"] = stats
    with forced_backend("xla"):
        ref = get_stack("openmp").run(chain).result
    if stats["mega"] < 1:
        problems.append(f"the fused stage did not dispatch through the "
                        f"megakernel: {stats}")
    if not _same(got, ref):
        problems.append(f"megakernel stage {_host(got)!r} != xla "
                        f"{_host(ref)!r}")
    info.update(_kernel_checks())
    _require(not problems, "; ".join(problems))
    return info


def phase_population(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The population at one candidate per bucket (an unbatched call each)
    and at 4 and ``n`` candidates per bucket (one vmapped call each), every
    candidate bit for bit against its own sequential ``run``."""
    proxy = _spec("terasort").to_benchmark()
    space = ParamSpace.from_dag(proxy.dag)
    n = cfg["population"]
    matrix = space.sample_dynamic(n, space.values(proxy.dag), seed=0)
    stack = get_stack("openmp")
    rng = jax.random.PRNGKey(0)
    seq = []
    for i in range(n):
        trial = proxy.clone()
        space.apply(trial.dag, matrix[i])
        seq.append(_host(stack.run(trial, rng=rng).result))
    info: Dict[str, Any] = {"candidates": n}
    problems: List[str] = []
    for width in sorted({1, 4, n}):
        t0 = time.perf_counter()
        pop = _host(stack.run_population(proxy, matrix, space=space, rng=rng,
                                         bucket_size=width).result)
        info[f"bucket{width}_s"] = time.perf_counter() - t0
        _require(np.isfinite(pop).all(), "population results are not finite")
        problems += [f"bucket {width} candidate {i}: {pop[i]!r} != run "
                     f"{seq[i]!r}" for i in range(n) if not _same(pop[i],
                                                                 seq[i])]
    _require(not problems, "; ".join(problems))
    return info


def phase_serve(cfg: Dict[str, Any]) -> Dict[str, Any]:
    trace = poisson_trace(n=cfg["requests"], seed=0, mix=SERVE_MIX)
    rep = serve(trace, stack="openmp", clock="wall")
    counts = rep.status_counts()
    _require(counts == {"ok": cfg["requests"]}, f"statuses {counts}")
    bad = {k: getattr(rep, k) for k in ("failures", "retries",
                                         "degraded_dispatches",
                                         "breaker_trips", "lost_requests",
                                         "retraces")
           if getattr(rep, k)}
    _require(not bad, f"serve counters not zero: {bad}")
    _require(all(np.isfinite(_host(r)).all() for r in rep.results),
             "a served result is not finite")
    ms = jax.devices()[0].memory_stats() or {}
    return {"requests": rep.n_requests, "latency_p50_s": rep.latency_s["p50"],
            "latency_p99_s": rep.latency_s["p99"],
            "throughput_rps": rep.throughput_rps,
            "dispatches": rep.dispatches,
            "peak_bytes_in_use": ms.get("peak_bytes_in_use", "not reported")}


def phase_tune(cfg: Dict[str, Any]) -> Dict[str, Any]:
    target = workload_fingerprint("terasort", cfg["scale"])
    proxy = _spec("terasort").to_benchmark()
    # tol 0: no early convergence, so both generations run
    res = PopulationTuner(target, population=cfg["tuner_pop"],
                          generations=2, tol=0.0, seed=0).tune(proxy)
    _require(len(res.history) == 2,
             f"tuner ran {len(res.history)} generations, not 2")
    acc = [g.best_accuracy for g in res.history]
    _require(all(np.isfinite(acc)), f"tuner accuracies {acc}")
    return {"generations": len(res.history), "best_accuracy": acc,
            "candidates_evaluated": res.candidates_evaluated}


def _sharded(x: Any, n: int) -> bool:
    s = getattr(x, "sharding", None)
    return (s is not None and len(s.device_set) == n
            and not s.is_fully_replicated)


def phase_mesh(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``MPIStack``/``SparkStack`` batch, population and serve axes over
    the whole mesh, bit for bit against ``openmp`` on one device.

    Population buckets and serving chunks hold ``lanes`` candidates or
    requests per device on the mesh and ``lanes`` on the one device, so
    both sides run the same per-device program: on the TPU a vmap over one
    lane compiles to other arithmetic than a vmap over several."""
    n_dev = len(jax.devices())
    _require(n_dev == cfg["chips"], f"mesh of {n_dev} devices, expected "
             f"{cfg['chips']}")
    info: Dict[str, Any] = {"devices": n_dev}
    problems: List[str] = []
    omp = get_stack("openmp")
    rngs = jax.random.split(jax.random.PRNGKey(3), cfg["batch"] * n_dev)
    proxy = _spec("terasort").to_benchmark()
    space = ParamSpace.from_dag(proxy.dag)
    matrix = space.sample_dynamic(cfg["population"], space.values(proxy.dag),
                                  seed=0)
    trace = poisson_trace(n=cfg["requests"], seed=0, mix=SERVE_MIX)
    lanes = cfg["lanes"]
    ref_pop = _host(omp.run_population(proxy, matrix, space=space,
                                       bucket_size=lanes).result)
    ref_serve = serve(trace, stack=omp, clock="wall", max_batch=lanes,
                      bucket_size=lanes).results
    for stack_name in ("mpi", "spark"):
        stack = get_stack(stack_name)
        outs: List[Any] = []
        for hook in ("_population_call", "_serve_call"):
            real = getattr(stack, hook)

            def spy(*a, _real=real):
                out = _real(*a)
                outs.append(out)
                return out

            setattr(stack, hook, spy)
        try:
            for name in SERVE_MIX:
                got = stack.run_batch(_spec(name), rngs).result
                ref = omp.run_batch(_spec(name), rngs).result
                if not _sharded(got, n_dev):
                    problems.append(f"{stack_name} run_batch {name}: "
                                    f"output not sharded: {got.sharding}")
                if not _same(got, ref):
                    problems.append(f"{stack_name} run_batch {name} differs "
                                    f"from openmp by {_max_diff(got, ref)}")
            pop = _host(stack.run_population(
                proxy, matrix, space=space, bucket_size=lanes * n_dev).result)
            n_pop_calls = len(outs)
            if not _same(pop, ref_pop):
                problems.append(f"{stack_name} run_population differs from "
                                f"openmp by {_max_diff(pop, ref_pop)}")
            rep = serve(trace, stack=stack, clock="wall",
                        max_batch=lanes * n_dev, bucket_size=lanes * n_dev)
            if rep.status_counts() != {"ok": cfg["requests"]}:
                problems.append(f"{stack_name} serve statuses "
                                f"{rep.status_counts()}")
            diffs = [_max_diff(a, b) for a, b in zip(rep.results, ref_serve)
                     if not _same(a, b)]
            if diffs:
                problems.append(f"{len(diffs)} {stack_name} serve results "
                                f"differ from openmp, by up to {max(diffs)}")
        finally:
            for hook in ("_population_call", "_serve_call"):
                delattr(stack, hook)
        unsharded = [i for i, o in enumerate(outs) if not _sharded(o, n_dev)]
        info[f"{stack_name}_sharded_calls"] = len(outs) - len(unsharded)
        if not outs[:n_pop_calls] or not outs[n_pop_calls:] or unsharded:
            problems.append(f"{stack_name}: {len(unsharded)} of {len(outs)} "
                            f"population/serve calls not sharded over "
                            f"{n_dev} devices")
    _require(not problems, "; ".join(problems))
    return info


PHASES: Dict[str, Callable[[Dict[str, Any]], Dict[str, Any]]] = {
    "device": phase_device,
    "originals": phase_originals,
    "proxies": phase_proxies,
    "population": phase_population,
    "serve": phase_serve,
    "tune": phase_tune,
}


def run(tiny: bool = False, chips: int = 1,
        emit: Callable[[str], None] = print) -> Dict[str, Any]:
    """Run the phases and return the result object (the script's last
    line).  ``ok`` is true only when every phase passed on a TPU; a
    failure of the ``device`` phase stops the run.  ``tiny`` rehearses on
    the CPU at small sizes with the Pallas backend under the
    interpreter."""
    cfg = {**SIZES["tiny" if tiny else "chip"], "tiny": tiny, "chips": chips}
    names = ["device", "mesh"] if chips > 1 else list(PHASES)
    phases = {**PHASES, "mesh": phase_mesh}
    report: Dict[str, Any] = {}
    failed: List[str] = []
    with _env(REPRO_BACKEND="pallas" if tiny else None):
        for name in names:
            t0 = time.perf_counter()
            try:
                info = phases[name](cfg)
                status = "ok"
            except Exception as exc:           # reported, then failed below
                traceback.print_exc()
                info, status = {}, f"FAIL {type(exc).__name__}: {exc}"
                failed.append(name)
            report[name] = {"status": status,
                            "seconds": time.perf_counter() - t0, **info}
            emit(f"phase {name}: " + json.dumps(report[name], default=str))
            if failed and name == "device":
                break
    dev = _device()
    return {"ok": not failed and not tiny and dev["platform"] == "tpu",
            "device": dev, "phases": report, "failed": failed}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh phase on four chips")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at small sizes (never ok: true)")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    res = run(tiny=args.tiny, chips=args.chips,
              emit=lambda s: print(s, flush=True))
    print("compile cache bytes at end: "
          f"{_dir_bytes(jax.config.jax_compilation_cache_dir)}", flush=True)
    if "device" in res["failed"] and not args.tiny:
        return 1                               # no accelerator: no result
    if res["ok"]:
        print(json.dumps({"ok": True, "device": res["device"]}), flush=True)
        return 0
    print(json.dumps({"ok": False, "device": res["device"],
                      "failed": res["failed"]}), flush=True)
    return 1 if res["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
