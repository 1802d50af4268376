"""Each row standardized: minus its mean, over its standard deviation
(population variance, plus 1e-6)."""

import jax.numpy as jnp

from . import rows


def apply(x, p, key):
    r = rows(x, p["chunk_size"])
    mean = jnp.mean(r, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(r - mean), axis=1, keepdims=True)
    return (r - mean) / jnp.sqrt(var + 1e-6)
