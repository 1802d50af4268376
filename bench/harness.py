"""The benchmark's run protocol: one cell, one seed, one window.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` finds the cell in ``BENCHMARK.json``, its configuration under
``bench/configs/`` and its traffic mix under ``bench/traffic/``, and:

1. turns on the program's persistent compile cache, and refuses to run
   where JAX finds no TPU or fewer chips than the cell asks for;
2. sets up: lowers the configuration's proxy on its stack and warms every
   executable the mix will use, then moves what set-up left on the
   Python heap out of the collector's reach (``setup_s`` runs from
   process start to the window's start);
3. measures for ``--seconds``, counting the compiles and the garbage
   collections inside the window, under the profiler when ``--trace 1``;
4. reads the device's peak memory, then checks a seeded sample of the
   window's answers against the plain reference (``bench/reference``);
5. prints each compared number beside its limit, and last the result's
   JSON line: the cell's end-to-end metrics with ``--trace 0``, its
   per-layer metrics (one reader each, ``bench/metrics/<name>.py``) with
   ``--trace 1``.

Nothing here knows a cell by name: a new cell is a ``workloads`` entry
with its configuration and mix files.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import traffic as traffic_mod
from bench import tracing

ROOT = pathlib.Path(__file__).resolve().parents[1]


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# files
# ---------------------------------------------------------------------------


def load_benchmark(root: pathlib.Path) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: List[Dict[str, Any]], name: str, what: str
         ) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def metric_reader(root: pathlib.Path, name: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


@dataclasses.dataclass
class Cell:
    """A cell as its files give it, on the device it runs on."""
    benchmark: Dict[str, Any]
    cell: Dict[str, Any]
    config: Dict[str, Any]
    mix: Dict[str, Any]
    stack: str
    device: Dict[str, Any]


def open_cell(root: pathlib.Path, cell, require_tpu: bool = True) -> Cell:
    """Read a cell's entry (its name in ``BENCHMARK.json``, or an entry
    of the same keys), configuration and mix, turn on the program's
    compile cache and look for the chips the cell asks for."""
    benchmark = load_benchmark(root)
    if isinstance(cell, str):
        cell = find(benchmark["workloads"], cell, "workload")
    entry = find(benchmark["configs"], cell["config"], "config")
    config = json.loads((root / entry["file"]).read_text())
    mix = traffic_mod.load(root, cell["traffic"])
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_default_matmul_precision",
                      config["precision"]["matmul_precision"])
    device = device_info(int(cell["chips"]), require_tpu)
    return Cell(benchmark, cell, config, mix,
                mix.get("stack") or config["stack"], device)


# ---------------------------------------------------------------------------
# compiles inside the window
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts JAX's compile events while ``armed``."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    CACHE = ("/jax/compilation_cache/cache_hits",
             "/jax/compilation_cache/cache_misses")

    def __init__(self):
        import jax.monitoring as mon
        self.armed = False
        self.counts = {"backend_compiles": 0, "jaxpr_traces": 0,
                       "cache_lookups": 0}
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if self.armed and event == self.BACKEND:
            self.counts["backend_compiles"] += 1
        elif self.armed and event == self.TRACE:
            self.counts["jaxpr_traces"] += 1

    def _event(self, event: str, **kw) -> None:
        if self.armed and event in self.CACHE:
            self.counts["cache_lookups"] += 1

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._duration)
        mon.unregister_event_listener(self._event)


class GcWatch:
    """Counts the garbage collector's passes and pauses while ``armed``."""

    def __init__(self):
        self.armed = False
        self.counts = {"collections": 0, "pause_s": 0.0, "max_pause_s": 0.0}
        self._t = 0.0
        gc.callbacks.append(self._callback)

    def _callback(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self.armed:
            pause = time.perf_counter() - self._t
            self.counts["collections"] += 1
            self.counts["pause_s"] += pause
            self.counts["max_pause_s"] = max(self.counts["max_pause_s"],
                                             pause)

    def close(self) -> None:
        gc.callbacks.remove(self._callback)


# ---------------------------------------------------------------------------
# the run record the metric readers see
# ---------------------------------------------------------------------------


class Run:
    """Everything one run measured, for the per-layer metric readers."""

    def __init__(self, c: Cell):
        self.cell = c.cell
        self.config = c.config
        self.mix = c.mix
        self.kind = c.mix["kind"]
        self.stack = c.stack
        self.trace: Optional[tracing.Reduction] = None
        self.reports: List[Any] = []        # RunReports of the window
        self.evals = 0                      # candidates counted
        self.serve_report: Any = None
        self.spans: Dict[str, float] = {}


# ---------------------------------------------------------------------------
# clients: the load each kind of traffic offers
# ---------------------------------------------------------------------------


def _spec_bench(config: Dict[str, Any]):
    from repro.api import ParamSpace, ProxySpec
    bench = ProxySpec.from_json(config["spec"]).to_benchmark()
    return bench, ParamSpace.from_dag(bench.dag)


class Population:
    """A tuner's closed loop over ``Stack.run_population``."""

    def __init__(self, config, mix, stack_name: str, seed: int,
                 seconds: float):
        from repro.api import get_stack
        self.seed = seed
        self.failed = 0
        self.attempted = 0
        self.bench, self.space = _spec_bench(config)
        self.stack = get_stack(stack_name)
        self.mask, self.names = traffic_mod.dynamic_fields(self.space)
        self.draws = traffic_mod.Draws(mix, [f for _, f in self.names])
        self.base = self.space.values(self.bench.dag)
        self.block = self.draws.block
        self.gens: List[Tuple[np.ndarray, Any, np.ndarray]] = []

    def _matrix(self, rows: np.ndarray) -> np.ndarray:
        m = np.tile(self.base, (rows.shape[0], 1))
        m[:, self.mask] = rows
        return m

    def _run(self, rows: np.ndarray, key):
        import jax
        with jax.profiler.TraceAnnotation("run_population"):
            rep = self.stack.run_population(self.bench, self._matrix(rows),
                                            rng=key, space=self.space)
        with jax.profiler.TraceAnnotation("result_sync"):
            res = np.asarray(rep.result)
        return rep, res

    def begin(self, seed: int) -> None:
        """Forget the last window; the next one draws from ``seed``."""
        self.seed, self.gens, self.failed, self.attempted = seed, [], 0, 0

    def setup(self) -> None:
        """Lower and compile through one generation at the spec's own
        parameters (every edge runs; weights are arguments, so the
        window's draws reuse every executable)."""
        import jax
        rows = np.tile(self.base[self.mask], (self.block, 1))
        self._run(rows, jax.random.fold_in(
            traffic_mod.base_key(self.seed), 1 << 30))

    def window(self, seconds: float, run: Run) -> Dict[str, float]:
        import jax
        base = traffic_mod.base_key(self.seed)
        t0 = time.perf_counter()
        end, last, g = t0 + seconds, t0, 0
        took = []
        with jax.profiler.TraceAnnotation("window"):
            while time.perf_counter() < end:
                rows = self.draws.rows(self.seed, g)
                key = jax.random.fold_in(base, g)
                t1 = time.perf_counter()
                rep, res = self._run(rows, key)
                t = time.perf_counter()
                took.append(t - t1)
                if t <= end:       # a generation finished in the window
                    last = t
                    self.gens.append((rows, key, res))
                    run.reports.append(rep)
                g += 1
        run.evals = self.block * len(self.gens)
        self.attempted = self.block * g
        print(f"generation seconds: {json.dumps(took)}", file=sys.stderr)
        if not self.gens:
            return {}
        return {"evals_per_s": run.evals / (last - t0)}

    def answers(self) -> List[Tuple[np.ndarray, Any, float]]:
        """``(dynamic row, key, answer)`` of every counted candidate."""
        return [(rows[i], key, float(res[i]))
                for rows, key, res in self.gens for i in range(len(rows))]

    def close(self) -> None:
        pass


class Serving:
    """Open-loop arrivals into the live ``ServingEngine``."""

    def __init__(self, config, mix, stack_name: str, seed: int,
                 seconds: float):
        from repro.serve.engine import ServingEngine
        self.config, self.mix, self.seed = config, mix, seed
        self.seconds = seconds
        self.bench, self.space = _spec_bench(config)
        self.dag = self.bench.dag
        self.mask, self.names = traffic_mod.dynamic_fields(self.space)
        self.draws = traffic_mod.Draws(mix, [f for _, f in self.names])
        self.block = self.draws.block
        self.engine = ServingEngine(stack=stack_name,
                                    max_batch=int(mix["max_batch"]))
        self.template = self.dag.dynamic_params()
        self.live = False
        self.records: List[Dict[str, Any]] = []
        self.failed = 0
        self.attempted = 0

    def _request(self, rid: int, row: np.ndarray, key):
        from repro.serve.engine import ProxyRequest
        dyn = [dict(d) for d in self.template]
        for (ei, field), v in zip(self.names, row):
            dtype = np.dtype(self.template[ei][field].dtype)
            dyn[ei][field] = np.asarray(round(v) if dtype.kind == "i"
                                        else v, dtype)
        return ProxyRequest(rid=rid, structure=self.config["name"],
                            dag=self.dag, dyn=tuple(dyn), rng=key,
                            arrival_s=0.0)

    def _keys(self, n: int) -> np.ndarray:
        import jax
        base = traffic_mod.base_key(self.seed)
        return np.asarray(jax.vmap(lambda i: jax.random.fold_in(base, i))(
            np.arange(n, dtype=np.uint32)))

    def begin(self, seed: int) -> None:
        """Forget the last window, draw the next one's request keys from
        ``seed`` and start the engine's dispatcher."""
        self.seed, self.records, self.failed, self.attempted = seed, [], 0, 0
        n = int(math.ceil(float(self.mix["rate_rps"]) * self.seconds * 1.5
                          + 2 * self.block))
        self.keys = self._keys(n + self.block)
        if not self.live:
            self.engine.start()
            self.live = True

    def setup(self) -> None:
        """Compile the engine's executables, then serve one block at the
        spec's own parameters through the live path."""
        self.engine.warmup([self.dag])
        self.begin(self.seed)
        base = self.space.values(self.dag)[self.mask]
        spare = len(self.keys) - self.block
        futs = [self.engine.submit(self._request(i, base,
                                                 self.keys[spare + i]))
                for i in range(self.block)]
        concurrent.futures.wait(futs)
        for f in futs:
            f.result()

    def window(self, seconds: float, run: Run) -> Dict[str, float]:
        import jax
        t0 = time.perf_counter()
        end = t0 + seconds
        futs = []
        with jax.profiler.TraceAnnotation("window"):
            for i, due in enumerate(traffic_mod.arrivals(self.mix,
                                                         self.seed)):
                due += t0
                if due >= end:
                    break
                rows = self.draws.rows(self.seed, i // self.block)
                row = rows[i % self.block]
                req = self._request(i, row, self.keys[i])
                pause = due - time.perf_counter()
                if pause > 0:
                    time.sleep(pause)
                rec = {"row": row, "key": self.keys[i], "due": due,
                       "sent": time.perf_counter(), "done": None}
                with jax.profiler.TraceAnnotation("submit"):
                    fut = self.engine.submit(req)
                fut.add_done_callback(
                    lambda f, rec=rec: rec.__setitem__(
                        "done", time.perf_counter()))
                rec["future"] = fut
                self.records.append(rec)
                futs.append(fut)
            with jax.profiler.TraceAnnotation("drain"):
                concurrent.futures.wait(futs,
                                        timeout=float(self.mix["drain_s"]))
        run.serve_report = self.engine.shutdown(drain=False)
        self.live = False
        lat = []
        for rec in self.records:
            f = rec["future"]
            ok = f.done() and f.exception() is None
            rec["answer"] = float(np.asarray(f.result())) if ok else None
            self.failed += 0 if ok else 1
            lat.append((rec["done"] - rec["due"]) * 1e3 if ok else math.inf)
        self.attempted = len(self.records)
        late = [rec["sent"] - rec["due"] for rec in self.records]
        print(f"generator lateness s: p50 {float(np.percentile(late, 50))!r}"
              f" max {float(max(late))!r} over {len(late)} requests",
              file=sys.stderr)
        if not lat:
            return {}
        return {"serve_p50_ms": nearest_rank(lat, 50),
                "serve_p95_ms": nearest_rank(lat, 95)}

    def answers(self) -> List[Tuple[np.ndarray, Any, float]]:
        return [(r["row"], r["key"], r["answer"]) for r in self.records
                if r["answer"] is not None]

    def close(self) -> None:
        if self.live:
            self.engine.shutdown(drain=False)
            self.live = False


CLIENTS = {"population": Population, "poisson": Serving}


def nearest_rank(values: List[float], q: float) -> float:
    """The ``q``-th percentile by nearest rank: a value that was measured
    (an unfinished request's infinite latency included)."""
    ranked = sorted(values)
    return float(ranked[max(math.ceil(q / 100.0 * len(ranked)) - 1, 0)])


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------


def readings(client, config: Dict[str, Any], mix: Dict[str, Any],
             seed: int, control: bool = False) -> List[Dict[str, float]]:
    """Per sampled answer: the program's gap to the reference and, with
    ``control``, the control's gap (the reference one precision lower,
    its arguments under ``check.control``), both over the sum of the reference sink's
    magnitudes (at least 1).

    The sample holds the costliest answer (most repeats), the
    ``check_largest`` answers of largest magnitude (a proxy whose last
    edges remove a mean answers about 0 whatever came before; one that
    does not carries every layer's error), and answers drawn from the
    seed up to ``check_sample``."""
    from bench.reference import Reference, dyn_of
    answers = client.answers()
    weight_cols = [j for j, (_, f) in enumerate(client.names)
                   if f == "weight"]
    cost = [float(np.sum(a[0][weight_cols])) for a in answers]
    size = [-abs(a[2]) if math.isfinite(a[2]) else -math.inf
            for a in answers]
    always = ([int(np.argmax(cost))] if cost else []) + \
        [int(i) for i in np.argsort(size, kind="stable")
         [:int(mix.get("check_largest", 0))]]
    picked = traffic_mod.sample(seed, len(answers),
                                int(mix["check_sample"]), always=always)
    spec = config["spec"]
    ref = Reference(spec, precision=config["precision"]["matmul_precision"])
    ctl = Reference(spec, **config["check"]["control"]) if control else None
    out = []
    for i in picked:
        row, key, answer = answers[i]
        dyn = dyn_of(client.names, row, len(spec["edges"]))
        want, l1 = ref.answer(dyn, key)
        scale = max(float(l1), 1.0)
        r = {"answer": answer, "reference": want, "l1": l1,
             "gap": abs(answer - want) / scale
             if math.isfinite(answer) else math.inf}
        if ctl is not None:
            c, _ = ctl.answer(dyn, key)
            r["control_gap"] = abs(c - want) / scale \
                if math.isfinite(c) else math.inf
        out.append(r)
    return out


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"this cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def parse(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(argv: List[str], *, root: pathlib.Path = ROOT,
             t_start: Optional[float] = None, require_tpu: bool = True,
             out=None) -> Dict[str, Any]:
    """One run of one cell; prints and returns the result's dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    out = sys.stdout if out is None else out
    args = parse(argv)
    c = open_cell(root, args.workload, require_tpu)
    benchmark, cell, config, mix = c.benchmark, c.cell, c.config, c.mix
    device = dict(c.device)
    run = Run(c)

    client = CLIENTS[mix["kind"]](config, mix, c.stack, args.seed,
                                  args.seconds)
    counter = CompileCounter()
    collector = GcWatch()
    log_dir = pathlib.Path(tempfile.mkdtemp(prefix="bench-trace-")) \
        if args.trace else None
    try:
        t_lower = time.perf_counter()
        client.setup()
        run.spans["lower_compile_s"] = time.perf_counter() - t_lower
        from repro.api import cache_stats
        traces0 = cache_stats()["traces"]
        gc.collect()
        gc.freeze()
        counter.armed = collector.armed = True
        setup_s = time.perf_counter() - t_start
        with (tracing.capture(log_dir) if log_dir
              else contextlib.nullcontext()):
            e2e = client.window(args.seconds, run)
        counter.armed = collector.armed = False
        gc.unfreeze()
        window_compiles = dict(counter.counts,
                               program_traces=int(cache_stats()["traces"]
                                                  - traces0))
        device["memory_peak_bytes"] = memory_peak()
        if log_dir is not None:
            run.trace = tracing.reduce(log_dir, int(cell["chips"]))
    finally:
        counter.close()
        collector.close()
        client.close()
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)
    print(f"garbage collections inside the window: "
          f"{json.dumps(collector.counts)}", file=sys.stderr)
    print(f"compiles inside the window: {json.dumps(window_compiles)}",
          file=out)

    limits = config["check"]
    got = readings(client, config, mix, args.seed)
    gap = max((r["gap"] for r in got), default=math.inf)
    checks = {"answer_gap": {"value": gap, "limit": limits["answer_gap"]}}
    print(f"answers compared: {len(got)} of {len(client.answers())}",
          file=sys.stderr)
    correct = (gap <= limits["answer_gap"] and client.failed == 0
               and len(got) > 0)

    metrics: Dict[str, Dict[str, Any]] = {}
    e2e["setup_s"] = setup_s
    cell_name = cell["name"]
    if not args.trace:
        for m in benchmark["end_to_end"]:
            if applies(m, cell_name) and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in benchmark["per_layer"]:
            if not applies(m, cell_name):
                continue
            v = metric_reader(root, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s

    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(client.attempted),
        "failed": int(client.failed), "metrics": metrics, "device": device}
    if run.trace is not None:
        result["breakdown"] = tracing.breakdown(run.trace)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return result


def main(argv: List[str], t_start: Optional[float] = None) -> int:
    try:
        run_cell(argv, t_start=t_start)
    except NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    return 0
