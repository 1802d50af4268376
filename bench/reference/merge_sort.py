"""Each row's two sorted halves merged: the row's first ``2 * (chunk //
2)`` values sorted ascending, a trailing odd value left in place.  The
merge is written as a sort: both give the same values."""

import jax.numpy as jnp

from . import rows


def apply(x, p, key):
    r = rows(x, p["chunk_size"])
    h = r.shape[1] // 2
    merged = jnp.sort(r[:, :2 * h], axis=1)
    return jnp.concatenate([merged, r[:, 2 * h:]], axis=1)
