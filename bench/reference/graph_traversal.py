"""Reachability from vertex 0 over the edges ``graph_construction``
reads, ``hops`` steps; each edge gets whether its destination was
reached."""

import jax.numpy as jnp

from .graph_construction import edges, vertices


def apply(x, p, key):
    v = vertices(x, p)
    src, dst = edges(x, v)
    reached = jnp.zeros((v,), x.dtype).at[0].set(1)
    for _ in range(int(p["extra"].get("hops", 4))):
        reached = jnp.maximum(reached,
                              jnp.zeros((v,), x.dtype).at[dst].max(reached[src]))
    return reached[dst % v]
