"""The plain reference against the program, at a small size: on the
openmp and hadoop stacks, on the XLA path and on the interpreted Pallas
kernels; and the control one precision lower, which the limit must
refuse."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import small_config
from bench import traffic
from bench.reference import Reference, dyn_of, fit, pairwise_sum

CONFIGS = ("kmeans_proxy", "terasort_proxy")
SIZE = 1 << 11


def _answers(cfg, stack, seed, n=8):
    from repro.api import ParamSpace, ProxySpec, get_stack
    bench = ProxySpec.from_json(cfg["spec"]).to_benchmark()
    space = ParamSpace.from_dag(bench.dag)
    mask, names = traffic.dynamic_fields(space)
    mix = dict(block=n, bounds={"weight": [0, 12]}, integer=["weight"])
    rows = traffic.Draws(mix, [f for _, f in names]).rows(seed, 0)
    m = np.tile(space.values(bench.dag), (n, 1))
    m[:, mask] = rows
    key = traffic.base_key(seed)
    rep = get_stack(stack).run_population(bench, m, rng=key, space=space)
    return rows, names, key, np.asarray(rep.result)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("stack", ["openmp", "hadoop"])
@pytest.mark.parametrize("name", CONFIGS)
def test_program_matches_the_reference(name, stack, backend, monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", backend)
    cfg = small_config(name, SIZE)
    rows, names, key, got = _answers(cfg, stack, seed=11)
    ref = Reference(cfg["spec"])
    gaps = []
    for row, answer in zip(rows, got):
        want, l1 = ref.answer(dyn_of(names, row, len(cfg["spec"]["edges"])),
                              key)
        gaps.append(abs(float(answer) - want) / max(l1, 1.0))
    assert max(gaps) <= cfg["check"]["answer_gap"], gaps


@pytest.mark.parametrize("name", CONFIGS)
def test_the_control_is_one_precision_below_and_a_dtype_control_fails(name):
    cfg = small_config(name, SIZE)
    args = cfg["check"]["control"]
    ctl = Reference(cfg["spec"], **args)
    if "precision" in args:
        # on the CPU every float32 product is exact, so the lower matrix
        # precision shows only in what the control asks of the compiler
        ei = [e["component"] for e in cfg["spec"]["edges"]].index(
            "euclidean_distance")
        text = ctl._repeat_fn(ei, tuple(sorted(
            cfg["spec"]["edges"][ei]["extra"].items()))).lower(
            jnp.zeros((SIZE,)), jax.random.PRNGKey(0)).as_text()
        assert "precision = [HIGH, HIGH]" in text and "HIGHEST" not in text
        return
    rows, names, key, _ = _answers(cfg, "openmp", seed=12)
    ref = Reference(cfg["spec"])
    gaps = []
    for row in rows:
        dyn = dyn_of(names, row, len(cfg["spec"]["edges"]))
        want, l1 = ref.answer(dyn, key)
        gaps.append(abs(ctl.answer(dyn, key)[0] - want) / max(l1, 1.0))
    assert max(gaps) > cfg["check"]["answer_gap"], gaps


def test_reference_imports_nothing_of_the_program():
    import pathlib
    src = "".join(p.read_text() for p in
                  pathlib.Path(__file__).resolve().parents[1]
                  .joinpath("reference").glob("*.py"))
    assert "repro" not in src


def test_glue_cuts_tiles_and_sums_pairwise():
    x = jnp.arange(5.0)
    assert fit(x, 3).tolist() == [0.0, 1.0, 2.0]
    assert fit(x, 12).tolist() == [0, 1, 2, 3, 4, 0, 1, 2, 3, 4, 0, 1]
    v = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0], jnp.bfloat16)
    assert float(pairwise_sum(v)) == 15.0
