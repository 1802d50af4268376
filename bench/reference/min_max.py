"""Each row of ``chunk`` values less its minimum, over its range (at
least 1e-6)."""

import jax.numpy as jnp

from . import rows


def apply(x, p, key):
    r = rows(x, p["chunk_size"])
    lo = jnp.min(r, axis=1, keepdims=True)
    hi = jnp.max(r, axis=1, keepdims=True)
    return (r - lo) / jnp.maximum(hi - lo, 1e-6)
