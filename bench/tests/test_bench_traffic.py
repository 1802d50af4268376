"""The traffic generator: the same draws from the same seed in any
process, and every seed offering the same work block by block."""

import itertools
import json
import subprocess
import sys

import numpy as np
import pytest

from benchkit import REPO
from bench import traffic

SEEDS = (0, 7, 2**31 + 12345, 2**40 + 3)

_DRAW = """
import json, sys
sys.path[:0] = [{repo!r}, {src!r}]
from bench import traffic
mix = traffic.load(__import__("pathlib").Path({repo!r}), {mix!r})
d = traffic.Draws(mix, ["weight"] * 5)
it = traffic.arrivals(dict(mix, rate_rps=25.0), {seed})
print(json.dumps({{"rows": d.rows({seed}, 3).tolist(),
                   "due": [next(it) for _ in range(40)],
                   "sample": traffic.sample({seed}, 100, 12, always=[99])}}))
"""


def _draw_here(mix_name, seed):
    mix = traffic.load(REPO, mix_name)
    d = traffic.Draws(mix, ["weight"] * 5)
    it = traffic.arrivals(dict(mix, rate_rps=25.0), seed)
    return {"rows": d.rows(seed, 3).tolist(),
            "due": [next(it) for _ in range(40)],
            "sample": traffic.sample(seed, 100, 12, always=[99])}


@pytest.mark.parametrize("mix_name", ["population", "poisson"])
def test_draws_repeat_in_another_process(mix_name):
    seed = SEEDS[2]
    code = _DRAW.format(repo=str(REPO), src=str(REPO / "src"),
                        mix=mix_name, seed=seed)
    got = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True, timeout=120)
    assert json.loads(got.stdout) == _draw_here(mix_name, seed)


@pytest.mark.parametrize("mix_name", ["population", "poisson"])
def test_every_seed_offers_the_same_work_in_another_order(mix_name):
    mix = traffic.load(REPO, mix_name)
    d = traffic.Draws(mix, ["weight"] * 3)
    blocks = [d.rows(s, b) for s in SEEDS for b in (0, 5)]
    sorted_cols = {tuple(np.sort(b, axis=0).ravel()) for b in blocks}
    assert len(sorted_cols) == 1
    orders = len({b.tobytes() for b in blocks})
    assert orders == len(blocks)
    lo, hi = mix["bounds"]["weight"]
    assert blocks[0].min() >= lo and blocks[0].max() <= hi
    assert np.all(blocks[0] == np.round(blocks[0]))


def test_gaps_are_the_same_set_at_the_mix_rate():
    mix = {"block": 16, "rate_rps": 40.0}
    g = [np.sort(traffic.gaps(mix, s, b)) for s in SEEDS for b in (0, 1)]
    assert all(np.array_equal(g[0], x) for x in g)
    assert abs(g[0].mean() * 40.0 - 1.0) < 0.1


def test_seeds_pair_the_same_gaps_and_draws_differently():
    mix = traffic.load(REPO, "poisson")
    d = traffic.Draws(mix, ["weight"] * 3)
    due = [list(itertools.islice(traffic.arrivals(mix, s), 48))
           for s in SEEDS]
    assert all(abs(x[-1] - due[0][-1]) < 1e-9 for x in due)
    assert len({tuple(x) for x in due}) == len(SEEDS)
    assert not np.array_equal(d.rows(SEEDS[0], 0), d.rows(SEEDS[1], 0))


def test_sample_keeps_the_forced_answers_and_its_size():
    s = traffic.sample(5, 50, 12, always=[49, 3])
    assert len(s) == 12 and {3, 49} <= set(s) and s == sorted(set(s))
    assert traffic.sample(5, 4, 12) == [0, 1, 2, 3]
    assert traffic.sample(5, 50, 12) == traffic.sample(5, 50, 12)
    assert traffic.sample(5, 50, 12) != traffic.sample(6, 50, 12)


def test_a_mix_needs_bounds_for_every_dynamic_parameter():
    mix = traffic.load(REPO, "population")
    with pytest.raises(ValueError, match="mix_rounds"):
        traffic.Draws(mix, ["weight", "mix_rounds"])


def test_keys_from_large_seeds_differ_in_their_high_bits():
    a = np.asarray(traffic.base_key(5))
    b = np.asarray(traffic.base_key(5 + 2**32))
    assert not np.array_equal(a, b)
