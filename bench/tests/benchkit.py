"""Helpers of the benchmark's own tests: small copies of its
configurations, and runs of a cell past the look for a chip."""

import copy
import io
import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

#: buffer length of the small copies of the configurations
SMALL = 1 << 12

#: a serving cell over the live engine, as a later change would add it:
#: a ``workloads`` entry and its metrics, over files already in ``bench/``
SERVING = {
    "workloads": [{"name": "kmeans.serve", "config": "kmeans_proxy",
                   "traffic": "poisson", "chips": 1,
                   "why": "open-loop Poisson requests into the live engine"}],
    "end_to_end": [{"name": n, "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["kmeans.serve"]}
                   for n in ("serve_p50_ms", "serve_p95_ms")],
    "per_layer": [{"name": "serve.device_idle", "unit": "%",
                   "better": "lower", "source": "device_trace",
                   "layer": "device", "moves": "serve_p95_ms",
                   "workloads": ["kmeans.serve"]},
                  {"name": "serve.queue_wait_p95_ms", "unit": "ms",
                   "better": "lower", "source": "program_span",
                   "layer": "serving", "moves": "serve_p95_ms",
                   "workloads": ["kmeans.serve"]}],
}


def small_config(name: str, size: int = SMALL) -> dict:
    """A configuration of the benchmark at ``size`` elements per buffer."""
    cfg = json.loads((REPO / "bench" / "configs" / f"{name}.json")
                     .read_text())
    spec = copy.deepcopy(cfg["spec"])
    spec["sources"] = {k: size for k in spec["sources"]}
    for e in spec["edges"]:
        e["data_size"] = size
    return dict(cfg, spec=spec)


def make_small_root(root: pathlib.Path) -> pathlib.Path:
    """A checkout-shaped directory whose ``BENCHMARK.json`` names the
    benchmark's cells and ``SERVING`` over small copies of its
    configurations, with the benchmark's own traffic and metric files."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for key, entries in SERVING.items():
        bench[key] += [e for e in entries
                       if e["name"] not in {x["name"] for x in bench[key]}]
    (root / "bench" / "configs").mkdir(parents=True)
    for c in bench["configs"]:
        (root / c["file"]).write_text(json.dumps(small_config(c["name"])))
    for d in ("traffic", "metrics"):
        shutil.copytree(REPO / "bench" / d, root / "bench" / d)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def with_size(root: pathlib.Path, size: int) -> pathlib.Path:
    """Give every configuration of a small root ``size``-element buffers:
    a structure no other test compiled, so a planted fault is traced."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        (root / c["file"]).write_text(json.dumps(small_config(c["name"],
                                                              size)))
    return root


def run_small(root, workload, *, seed=3_000_000_017, seconds=1.5, trace=0):
    """One run of a cell of a small root on the CPU; returns the result's
    dict and the lines printed before it."""
    from bench.harness import run_cell
    out = io.StringIO()
    result = run_cell(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, require_tpu=False, out=out)
    lines = out.getvalue().splitlines()
    assert json.loads(lines[-1]) == result
    return result, lines[:-1]
