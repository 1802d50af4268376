"""The PageRank proxy against its plain reference at a small size: on the
openmp, hadoop and spark stacks, on the XLA path and on the interpreted
Pallas kernels; the control one precision below the configuration's
products; the ``spark.place_idle`` reader on a made-up trace and in a
traced small run of ``pagerank.pop``; the cell's entries in
``BENCHMARK.json``; and the faults of ``test_bench_faults`` planted under
that cell."""

import json
import types

import jax
import jax.numpy as jnp
import pytest

from benchkit import REPO, run_small, small_config, with_size
from bench import tracing
from bench.harness import metric_reader
from bench.reference import Reference, dyn_of
from test_bench_faults import FAULTS
from test_bench_reference import _answers
from test_bench_spans import _ev, _plane

NAME = "pagerank_proxy"
CELL = "pagerank.pop"
SIZE = 1 << 11
#: matrix product precisions, lowest first
PRECISIONS = ("default", "high", "highest")

#: the metrics whose ``workloads`` the cell joins
JOINS = ("evals_per_s", "pop.device_idle", "pop.dispatch_idle",
         "setup.cost_analysis_s")


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("stack", ["openmp", "hadoop", "spark"])
def test_program_matches_the_reference(stack, backend, monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", backend)
    cfg = small_config(NAME, SIZE)
    rows, names, key, got = _answers(cfg, stack, seed=13)
    ref = Reference(cfg["spec"],
                    precision=cfg["precision"]["matmul_precision"])
    gaps = []
    for row, answer in zip(rows, got):
        want, l1 = ref.answer(dyn_of(names, row, len(cfg["spec"]["edges"])),
                              key)
        gaps.append(abs(float(answer) - want) / max(l1, 1.0))
    assert max(gaps) <= cfg["check"]["answer_gap"], gaps


def test_the_control_computes_the_product_one_precision_below():
    cfg = small_config(NAME, SIZE)
    stated = cfg["precision"]["matmul_precision"]
    args = cfg["check"]["control"]
    assert PRECISIONS.index(args["precision"]) == \
        PRECISIONS.index(stated) - 1
    ei = [e["component"] for e in cfg["spec"]["edges"]].index(
        "matrix_multiplication")
    extra = tuple(sorted(cfg["spec"]["edges"][ei]["extra"].items()))

    def lowered(ref):
        return ref._repeat_fn(ei, extra).lower(
            jnp.zeros((SIZE,)), jax.random.PRNGKey(0)).as_text()

    # on the CPU every float32 product is exact, so the lower precision
    # shows only in what the control asks of the compiler
    text = lowered(Reference(cfg["spec"], **args))
    assert "precision = [HIGH, HIGH]" in text and "HIGHEST" not in text
    assert "precision = [HIGHEST, HIGHEST]" in lowered(
        Reference(cfg["spec"], precision=stated))


def _reduction(place=True):
    """Window [0, 1000); device 0 busy [100, 300) and [500, 600).  Two
    buckets' dispatches, [250, 450) and [620, 900), each placing its
    parameters first: [250, 350) and [620, 700)."""
    main = [_ev("window", 0, 1000), _ev("run_population", 0, 1000),
            _ev("stack.dispatch", 250, 450),
            _ev("stack.dispatch", 620, 900)]
    if place:
        main += [_ev("spark.place", 250, 350), _ev("spark.place", 620, 700)]
    host = _plane("/host:CPU", {"main": main})
    dev = _plane("/device:TPU:0", {"XLA Ops": [_ev("fusion.1", 100, 300),
                                               _ev("fusion.2", 500, 600)]})
    return tracing.Reduction(types.SimpleNamespace(planes=[host, dev]),
                             devices=1)


def _run(red, kind="population"):
    return types.SimpleNamespace(kind=kind, trace=red, stack="spark",
                                 reports=[], evals=0)


def test_the_place_reader_reads_idle_time_under_the_span():
    read = metric_reader(REPO, "spark.place_idle")
    red = _reduction()
    # idle under spark.place: [300, 350) and [620, 700)
    assert read(_run(red)) == pytest.approx(13.0)
    # under a dispatch: [300, 450) and [620, 900)
    assert metric_reader(REPO, "pop.dispatch_idle")(_run(red)) \
        == pytest.approx(43.0)
    assert read(_run(_reduction(place=False))) is None
    assert read(_run(red, kind="poisson")) is None
    assert read(_run(None)) is None


def test_the_benchmark_holds_one_configuration_and_one_cell():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    configs = [c for c in bench["configs"] if c["name"] == NAME]
    cells = [w for w in bench["workloads"] if w["config"] == NAME]
    assert [c["file"] for c in configs] == [f"bench/configs/{NAME}.json"]
    assert configs[0]["reduced"] == ["data_size"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == \
        [(CELL, "population", 1)]
    metrics = bench["end_to_end"] + bench["per_layer"]
    joined = {m["name"] for m in metrics if CELL in m.get("workloads", ())}
    assert joined == set(JOINS) | {"spark.place_idle"}
    place = [m for m in bench["per_layer"] if m["name"] == "spark.place_idle"]
    assert (place[0]["layer"], place[0]["moves"], place[0]["source"]) == \
        ("stacks", "evals_per_s", "device_trace")


def test_a_traced_pagerank_run_prints_the_place_idle(small_root):
    result, _ = run_small(small_root, CELL, trace=1)
    assert result["correct"] is True
    got = result["metrics"]
    assert {"spark.place_idle", "pop.dispatch_idle", "pop.device_idle",
            "setup.cost_analysis_s"} <= set(got)
    assert 0 <= got["spark.place_idle"]["value"] \
        <= got["pop.dispatch_idle"]["value"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(small_root, monkeypatch, fault):
    # a buffer size no other test compiles, so the fault is traced
    size = {"answer_altered": 9 << 10, "state_unchanged": 11 << 10,
            "half_left_out": 13 << 10}[fault]
    FAULTS[fault](monkeypatch)
    result, _ = run_small(with_size(small_root, size), CELL)
    assert result["correct"] is False
    assert result["checks"]["answer_gap"]["value"] > \
        result["checks"]["answer_gap"]["limit"]
