"""One minus the cosine similarity of each point row to each centroid
(centroids as in ``euclidean_distance``)."""

import jax.numpy as jnp

from . import fit, rows


def apply(x, p, key):
    pts = rows(x, p["chunk_size"])
    k = int(p["extra"].get("centers", 16))
    ctr = fit(x[::-1], k * pts.shape[1]).reshape(k, -1)
    num = pts @ ctr.T
    den = (jnp.sqrt(jnp.sum(pts * pts, 1, keepdims=True))
           * jnp.sqrt(jnp.sum(ctr * ctr, 1)) + 1e-6)
    return 1.0 - num / den
