"""Point-to-centroid squared distances, scaled by the row length.

Rows of ``chunk`` values are points; the centroids are the first
``centers`` rows of the reversed buffer.  The product runs at the
reference's precision."""

import jax.numpy as jnp

from . import fit, rows


def apply(x, p, key):
    pts = rows(x, p["chunk_size"])
    k = int(p["extra"].get("centers", 16))
    ctr = fit(x[::-1], k * pts.shape[1]).reshape(k, -1)
    d2 = (jnp.sum(pts * pts, 1, keepdims=True) - 2.0 * (pts @ ctr.T)
          + jnp.sum(ctr * ctr, 1))
    return d2 * (1.0 / pts.shape[1])
