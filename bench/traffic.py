"""The one traffic generator: reads a mix's data file, draws from ``--seed``.

A mix file (``bench/traffic/<mix>.json``) holds parameters only.  Its
``kind`` says how the harness drives the system:

* ``population`` — a tuner's closed loop: generations of ``block``
  candidates, each sent through ``Stack.run_population`` when the last
  one is done.
* ``poisson`` — open-loop arrivals at ``rate_rps`` into the live serving
  engine, one request per arrival.

Draws are Latin-hypercube samples in blocks of ``block``.  For each
dynamic parameter, a block holds the ``block`` stratum midpoints of the
log-uniform law over its ``bounds`` (the arithmetic of the program's
``ParamSpace.sample``: uniform on ``[log max(lo, 1e-3), log hi]``,
integers rounded inside the bounds), in an order drawn from the seed.  A
Poisson block's gaps are the ``block`` stratum midpoints of the
exponential law at ``rate_rps``, in an order drawn from the seed.  So
every seed offers the same work and the same gaps, block by block, in
another order, and two seeds differ only by how they pair them.
"""

from __future__ import annotations

import json
import math
import pathlib
from typing import Any, Dict, Iterator, List, Sequence, Tuple

import jax
import numpy as np


def load(root: pathlib.Path, name: str) -> Dict[str, Any]:
    path = root / "bench" / "traffic" / f"{name}.json"
    mix = json.loads(path.read_text())
    if mix.get("kind") not in ("population", "poisson"):
        raise ValueError(f"{path}: kind must be 'population' or 'poisson'")
    return mix


def base_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the low and high 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), *stream])


def strata(block: int) -> np.ndarray:
    """The stratum midpoints ``(k + 0.5) / block``."""
    return (np.arange(block) + 0.5) / block


def leaf_values(lo: float, hi: float, integer: bool, block: int
                ) -> np.ndarray:
    """One block's values of a parameter: log-uniform stratum midpoints
    in ``[lo, hi]``, rounded inside the bounds for an integer one."""
    llo, lhi = math.log(max(lo, 1e-3)), math.log(max(hi, 1e-3))
    v = np.exp(llo + strata(block) * (lhi - llo))
    v = np.clip(v, lo, hi)
    if integer:
        ilo, ihi = math.ceil(lo), math.floor(hi)
        v = np.clip(np.round(v), ilo, ihi)
    return v


class Draws:
    """The dynamic-parameter rows of a mix: block ``b`` of seed ``s``.

    ``fields`` names each dynamic column's parameter (``weight``, or an
    extra's key); the mix gives each such parameter ``[lo, hi]`` under
    ``bounds`` and lists the integer ones under ``integer``."""

    def __init__(self, mix: Dict[str, Any], fields: Sequence[str]):
        self.block = int(mix["block"])
        bounds = mix["bounds"]
        missing = sorted(set(fields) - set(bounds))
        if missing:
            raise ValueError(f"the mix gives no bounds for {missing}")
        integer = set(mix.get("integer", []))
        self.columns = [leaf_values(*bounds[f], f in integer, self.block)
                        for f in fields]

    def rows(self, seed: int, block: int) -> np.ndarray:
        """``(block, n_fields)``: each column's values in a seeded order."""
        rng = _rng(seed, 1, block)
        return np.stack([c[rng.permutation(self.block)]
                         for c in self.columns], axis=1)


def gaps(mix: Dict[str, Any], seed: int, block: int) -> np.ndarray:
    """One block's inter-arrival gaps in seconds, in a seeded order."""
    n = int(mix["block"])
    g = -np.log1p(-strata(n)) / float(mix["rate_rps"])
    return g[_rng(seed, 2, block).permutation(n)]


def arrivals(mix: Dict[str, Any], seed: int) -> Iterator[float]:
    """Due times in seconds from the stream's start, without end."""
    t, b = 0.0, 0
    while True:
        for g in gaps(mix, seed, b):
            t += float(g)
            yield t
        b += 1


def sample(seed: int, n_done: int, k: int, always: Sequence[int] = ()
           ) -> List[int]:
    """Indices of ``k`` of ``n_done`` finished answers drawn from the
    seed, with the ``always`` ones among them."""
    keep = [int(i) for i in always if 0 <= i < n_done]
    rest = [int(i) for i in _rng(seed, 3).permutation(n_done)
            if int(i) not in keep]
    return sorted(keep + rest[:max(k - len(keep), 0)])


def dynamic_fields(space) -> Tuple[np.ndarray, List[Tuple[int, str]]]:
    """The dynamic columns of a ``ParamSpace`` and their ``(edge,
    field)`` names, in leaf order."""
    mask = space.dynamic_mask()
    names = [(l.edge_idx, l.field) for l in space.leaves if l.dynamic]
    return mask, names
