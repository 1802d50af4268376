"""The program's own spans and transfer counters: population runs under
the profiler record every span, the hadoop spill's four steps nest in
order inside their stage, ``RunReport.h2d_bytes`` / ``d2h_bytes`` equal
the bytes counted by hand, and a live serving session records its
spans."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import ParamSpace, get_stack
from repro.core import engine
from repro.core.dag import Edge, ProxyDAG
from repro.core.dwarfs import ComponentParams
from repro.serve.engine import ProxyRequest, ServingEngine

#: elements per buffer: a size no other test compiles, so the engine
#: analyses its bodies afresh
N = 384
F32 = 4
POP = 4
PROGRAM = ("stack.", "hadoop.", "engine.", "serve.")


def _acc_dag() -> ProxyDAG:
    """Two edges into one node: the second accumulates onto the first's
    output, so its stage uploads the node as ``prev``."""
    return ProxyDAG(
        "spans_acc", {"src": N},
        [Edge("hash", ["src"], "a",
              ComponentParams(data_size=N, chunk_size=64, weight=1,
                              extra={"rounds": 1})),
         Edge("min_max", ["src"], "a",
              ComponentParams(data_size=N, chunk_size=64, weight=2))],
        "a")


def _candidates(dag):
    space = ParamSpace.from_dag(dag)
    return space, space.sample_dynamic(POP, space.values(dag), seed=0)


@pytest.fixture
def unfused(monkeypatch):
    monkeypatch.setenv("REPRO_FUSION_THRESHOLD", "0")


def _host_events(log_dir: pathlib.Path):
    """``{line id: [(name, start, end, stats)]}`` of the host planes."""
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    prof = ProfileData.from_file(str(files[-1]))
    lines = {}
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            lines[(plane.name, k)] = [
                (e.name, e.start_ns, e.end_ns,
                 dict(e.stats) if e.name.startswith(PROGRAM) else {})
                for e in line.events]
    return lines


def _traced(log_dir, fn):
    jax.profiler.start_trace(str(log_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, _host_events(log_dir)


def _names(lines):
    return {ev[0] for evs in lines.values() for ev in evs}


STEPS = ["hadoop.h2d", "hadoop.dispatch", "hadoop.wait", "hadoop.d2h"]


def test_hadoop_population_spans_nest_inside_their_stage(tmp_path, unfused):
    dag = _acc_dag()
    space, matrix = _candidates(dag)
    a0 = engine.stats()["analyze_s"]
    rep, lines = _traced(tmp_path, lambda: get_stack("hadoop").run_population(
        dag, matrix, space=space, bucket_size=2))
    assert engine.stats()["analyze_s"] > a0
    assert {"hadoop.init", "hadoop.stage", "engine.analyze"} | set(STEPS) \
        <= _names(lines)
    stages = 0
    for evs in lines.values():
        for name, s, e, stats in evs:
            if name != "hadoop.stage":
                continue
            stages += 1
            inner = sorted((ev for ev in evs if s <= ev[1] and ev[2] <= e
                            and ev[0] in STEPS), key=lambda ev: ev[1])
            assert [ev[0] for ev in inner] == STEPS
            assert set(stats) == {"bucket", "stage"}
        steps = [ev for ev in evs if ev[0] in STEPS]
        assert all(any(st[0] == "hadoop.stage" and st[1] <= s and e <= st[2]
                       for st in evs) for _, s, e, _ in steps)
    # two buckets, each two stages and the finalize stage
    assert stages == 2 * 3


def test_hadoop_run_spans_leave_the_result_on_the_device(tmp_path, unfused):
    rep, lines = _traced(tmp_path,
                         lambda: get_stack("hadoop").run(_acc_dag()))
    assert isinstance(rep.result, jax.Array)
    finalize = [ev for evs in lines.values() for ev in evs
                if ev[0] == "hadoop.stage" and ev[3]["stage"] == "finalize"]
    assert len(finalize) == 1


@pytest.mark.parametrize("bucket_size", [1, 2])
def test_openmp_population_records_every_span(tmp_path, bucket_size):
    dag = _acc_dag()
    space, matrix = _candidates(dag)
    rep, lines = _traced(tmp_path, lambda: get_stack("openmp").run_population(
        dag, matrix, space=space, bucket_size=bucket_size))
    assert {"stack.schedule", "stack.dispatch", "stack.gather",
            "stack.assemble"} <= _names(lines)
    buckets = sorted(ev[3]["bucket"] for evs in lines.values()
                     for ev in evs if ev[0] == "stack.dispatch")
    assert buckets == list(range(POP // bucket_size))


def test_hadoop_population_bytes_match_the_count_by_hand(unfused):
    dag = _acc_dag()
    space, matrix = _candidates(dag)
    nb = 2
    rep = get_stack("hadoop").run_population(dag, matrix, space=space,
                                             bucket_size=nb)
    r = np.asarray(rep.result).dtype.itemsize
    node, batch = N * F32, nb * N * F32
    per_bucket_up = (nb * 4                    # candidate indices, int32
                     + node                    # stage 0: src
                     + node + batch            # stage 1: src, prev
                     + node + batch)           # finalize: every node
    assert rep.h2d_bytes == (POP // nb) * per_bucket_up + POP * r
    assert rep.d2h_bytes == node + (POP // nb) * (2 * batch + nb * r)
    # the modelled spill keeps its formula: sources once, stages twice
    assert rep.io_bytes == node + (POP // nb) * 2 * 2 * batch


def test_hadoop_run_bytes_match_the_count_by_hand(unfused):
    rep = get_stack("hadoop").run(_acc_dag())
    node = N * F32
    assert rep.h2d_bytes == 5 * node           # src; src, prev; src, a
    assert rep.d2h_bytes == 3 * node           # src and two stages
    assert rep.io_bytes == node + 2 * 2 * node


def test_openmp_population_bytes_are_the_params_and_results():
    dag = _acc_dag()
    space, matrix = _candidates(dag)
    dynb = space.stack_candidates(dag, matrix)
    params = sum(v.nbytes for d in dynb for v in d.values())
    rep = get_stack("openmp").run_population(dag, matrix, space=space,
                                             bucket_size=1)
    r = np.asarray(rep.result).dtype.itemsize
    assert rep.d2h_bytes == params + POP * r
    assert rep.h2d_bytes == params + POP * r
    assert rep.io_bytes == 0.0


def test_hadoop_map_reduce_and_opaque_fn_count_both_directions():
    stack = get_stack("hadoop")
    data = jnp.arange(64, dtype=jnp.float32)
    rep = stack.map_reduce(lambda c: c * 2.0, jnp.sum, data, n_chunks=4)
    assert rep.d2h_bytes == 2 * data.nbytes    # the input, the map outputs
    assert rep.h2d_bytes == 2 * data.nbytes    # the chunks, the shuffle
    assert float(rep.result) == float(jnp.sum(data * 2.0))
    rep = stack.run(lambda x: x + 1.0, jnp.ones(8, jnp.float32))
    assert (rep.h2d_bytes, rep.d2h_bytes, rep.io_bytes) == (32, 32, 64)
    assert rep.to_json()["h2d_bytes"] == 32


def test_live_serving_records_its_spans(tmp_path):
    dag = _acc_dag()
    eng = ServingEngine(stack="openmp", max_batch=2)
    eng.warmup([dag])

    def live():
        eng.start()
        futs = [eng.submit(ProxyRequest(
            rid=0, structure="spans_acc", dag=dag, dyn=dag.dynamic_params(),
            rng=jax.random.PRNGKey(i), arrival_s=0.0)) for i in range(3)]
        for f in futs:
            f.result(timeout=60)
        return eng.shutdown()

    rep, lines = _traced(tmp_path, live)
    assert rep.status_counts() == {"ok": 3}
    assert rep.resources["host_rss_peak_bytes"] > 0
    names = {"serve.group", "serve.cost", "serve.dispatch", "serve.sync"}
    assert names <= _names(lines)
    for evs in lines.values():
        for name, _, _, stats in evs:
            if name in names:
                assert "rid" in stats


def test_traffic_counts_every_copy_from_many_threads():
    import sys
    from concurrent.futures import ThreadPoolExecutor
    from repro.api.stack import _Traffic
    io = _Traffic()
    host = np.ones(16, np.float32)
    dev = jnp.ones(8, jnp.float32)

    def copy(_):
        for _ in range(50):
            io.up(host)
            io.down(dev)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(max_workers=32) as pool:
            list(pool.map(copy, range(64), timeout=120))
    finally:
        sys.setswitchinterval(interval)
    assert io.h2d == 64 * 50 * host.nbytes
    assert io.d2h == 64 * 50 * dev.nbytes
