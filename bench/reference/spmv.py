"""One step of PageRank without damping, from the uniform rank, over the
edges ``graph_construction`` reads: each edge carries its source's rank
over the source's out-degree (at least 1) to its destination, summed
with ``.at[].add`` in edge order; each edge gets its destination's new
rank."""

import jax.numpy as jnp

from .graph_construction import edges, vertices


def apply(x, p, key):
    v = vertices(x, p)
    src, dst = edges(x, v)
    out_deg = jnp.zeros((v,), x.dtype).at[src].add(1)
    rank = jnp.full((v,), 1.0 / v, x.dtype)
    share = rank[src] / jnp.maximum(out_deg[src], 1)
    return jnp.zeros((v,), x.dtype).at[dst].add(share)[dst]
