"""The plain references of the PageRank proxy's matrix, graph and sort
components (``bench/reference/<component>.py``) give the same bits as
the dwarfs' own ``apply`` on seeded inputs."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dwarfs import ComponentParams
from repro.core.dwarfs.base import get_component

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

from bench.reference import component  # noqa: E402

N = 1 << 12
CHUNK = 256


@pytest.mark.parametrize("name,extra", [
    ("matrix_construction", {}),
    ("matrix_multiplication", {}),
    ("spmv", {"vertices": 4096}),
    ("min_max", {}),
])
def test_reference_gives_the_dwarfs_bits(name, extra):
    x = jax.random.normal(jax.random.PRNGKey(len(name)), (N,), jnp.float32)
    key = jax.random.PRNGKey(7)
    p = ComponentParams(data_size=N, chunk_size=CHUNK, weight=1, extra=extra)
    ref = dict(data_size=N, chunk_size=CHUNK, weight=1, extra=extra)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda v: get_component(name).apply(v, p, key))(x)
        got = jax.jit(lambda v: component(name).apply(v, ref, key))(x)
    want = np.asarray(want, np.float32).reshape(-1)
    got = np.asarray(got, np.float32).reshape(-1)
    assert got.shape == want.shape
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
