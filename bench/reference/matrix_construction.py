"""Each row of ``chunk`` values plus a tenth of the outer product of the
row means and the column means (the PageRank matrix build)."""

import jax.numpy as jnp

from . import rows


def apply(x, p, key):
    a = rows(x, p["chunk_size"])
    u = jnp.mean(a, axis=1)
    v = jnp.mean(a, axis=0)
    return a + 0.1 * (u[:, None] * v[None, :])
