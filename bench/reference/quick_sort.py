"""Each row of ``chunk`` values sorted ascending."""

import jax.numpy as jnp

from . import rows


def apply(x, p, key):
    return jnp.sort(rows(x, p["chunk_size"]), axis=1)
