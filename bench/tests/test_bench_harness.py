"""The run protocol on the CPU, past the look for a chip: every cell
runs and checks correct, no compile lands in the window, the end-to-end
and per-layer metrics follow ``BENCHMARK.json``, a new cell needs only
new files and entries, and a run without a TPU prints no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchkit import REPO, SERVING, run_small, small_config

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"] + SERVING["workloads"]
         if w["chips"] == 1]


@pytest.mark.parametrize("workload", CELLS)
def test_each_cell_runs_correct_with_no_compile_in_the_window(
        small_root, workload):
    result, lines = run_small(small_root, workload)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    bench = json.loads((small_root / "BENCHMARK.json").read_text())
    names = {m["name"] for m in bench["end_to_end"]
             if workload in m.get("workloads", [workload])}
    assert set(result["metrics"]) == names
    assert all(v["value"] > 0 for v in result["metrics"].values())
    compiles = json.loads(lines[-1].split(": ", 1)[1])
    assert compiles["backend_compiles"] == 0
    assert compiles["program_traces"] == 0
    assert list(result)[-1] == "checks"


def test_a_new_cell_is_files_and_entries_only(small_root):
    root = small_root
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = small_config("kmeans_proxy")
    cfg["name"] = "kmeans_wide"
    cfg["spec"]["edges"][0]["extra"]["centers"] = 32
    (root / "bench" / "configs" / "kmeans_wide.json").write_text(
        json.dumps(cfg))
    mix = json.loads((root / "bench" / "traffic" / "population.json")
                     .read_text())
    (root / "bench" / "traffic" / "population_small.json").write_text(
        json.dumps(dict(mix, block=8, bounds={"weight": [0, 6]})))
    (root / "bench" / "metrics" / "pop.evals_seen.py").write_text(
        "def read(run):\n    return float(run.evals) or None\n")
    bench["configs"].append(dict(bench["configs"][0], name="kmeans_wide",
                                 file="bench/configs/kmeans_wide.json"))
    bench["workloads"].append({"name": "kmeans_wide.pop",
                               "config": "kmeans_wide",
                               "traffic": "population_small", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "pop.evals_seen", "unit": "evals",
                               "better": "higher", "source": "host_clock",
                               "layer": "stacks", "moves": "evals_per_s",
                               "workloads": ["kmeans_wide.pop"]})
    for m in bench["end_to_end"]:
        if "evals_per_s" == m["name"]:
            m["workloads"].append("kmeans_wide.pop")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    result, _ = run_small(root, "kmeans_wide.pop")
    assert result["correct"] and "evals_per_s" in result["metrics"]
    traced, _ = run_small(root, "kmeans_wide.pop", trace=1)
    assert traced["correct"]
    assert traced["metrics"]["pop.evals_seen"]["value"] % 8 == 0
    assert "setup.lower_compile_s" in traced["metrics"]
    assert "evals_per_s" not in traced["metrics"]
    assert {"busy_s", "window_s"} <= set(traced["device"])


def _run_script(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kmeans.pop",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = _run_script(REPO, env)
    assert got.returncode != 0
    assert "TPU" in got.stderr
    assert not [l for l in got.stdout.splitlines() if l.startswith("{")]


def test_the_benchmark_alone_fails_and_prints_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    got = _run_script(tmp_path, dict(env, JAX_PLATFORMS="cpu"))
    assert got.returncode != 0
    assert not [l for l in got.stdout.splitlines() if l.startswith("{")]
