"""With the timed path broken underneath, a run past the look for a chip
checks ``correct`` false: an answer altered where it is produced, a
repeat that returns its input unchanged, and half of each population
left out (its candidates answered with the other half's)."""

import numpy as np
import pytest

from benchkit import run_small, with_size


def _alter_answer(monkeypatch):
    from repro.core import dag
    orig = dag._checksum
    monkeypatch.setattr(dag, "_checksum", lambda v: orig(v) + 1.0)


def _unchanged_state(monkeypatch):
    from repro.core import schedule
    from repro.core.dwarfs.base import fit_buffer
    monkeypatch.setattr(
        schedule, "_edge_out",
        lambda e, ei, x, rng, dyn=None: fit_buffer(x, e.params.data_size))
    monkeypatch.setattr(
        schedule, "_fused_out",
        lambda members, x, rng, dyn: fit_buffer(
            x, members[0][1].params.data_size))
    monkeypatch.setattr(schedule, "_mega_out", lambda *a: None)


def _half_left_out(monkeypatch):
    from repro.api import stack
    orig = stack.Stack.run_population

    def half(self, executable, candidates, **kw):
        n = candidates.shape[0]
        kept = candidates[: n // 2]
        return orig(self, executable,
                    np.concatenate([kept, kept[: n - n // 2]]), **kw)

    monkeypatch.setattr(stack.Stack, "run_population", half)


FAULTS = {"answer_altered": _alter_answer,
          "state_unchanged": _unchanged_state,
          "half_left_out": _half_left_out}

CASES = [("kmeans.pop", f) for f in FAULTS] + \
        [("terasort.pop", f) for f in FAULTS] + \
        [("kmeans.serve", f) for f in ("answer_altered", "state_unchanged")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_a_broken_timed_path_is_not_correct(small_root, monkeypatch,
                                            workload, fault):
    # a buffer size no other test compiles, so the fault is traced
    size = {"answer_altered": 3 << 11, "state_unchanged": 5 << 11,
            "half_left_out": 7 << 11}[fault]
    root = with_size(small_root, size)
    FAULTS[fault](monkeypatch)
    result, _ = run_small(root, workload)
    assert result["correct"] is False
    assert result["checks"]["answer_gap"]["value"] > \
        result["checks"]["answer_gap"]["limit"]
