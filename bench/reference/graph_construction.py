"""Consecutive value pairs as edges between ``vertices`` vertices (bit
pattern modulo the vertex count); each edge gets its source's out-degree
plus its destination's in-degree."""

import jax.numpy as jnp

from . import as_u32


def edges(x, v):
    u = as_u32(x)
    n2 = (u.shape[0] // 2) * 2
    src = (u[:n2:2] % jnp.uint32(v)).astype(jnp.int32)
    dst = (u[1:n2:2] % jnp.uint32(v)).astype(jnp.int32)
    return src, dst


def vertices(x, p):
    return int(p["extra"].get("vertices", max(64, x.shape[0] // 8)))


def apply(x, p, key):
    src, dst = edges(x, vertices(x, p))
    v = vertices(x, p)
    out_deg = jnp.zeros((v,), x.dtype).at[src].add(1)
    in_deg = jnp.zeros((v,), x.dtype).at[dst].add(1)
    return out_deg[src] + in_deg[dst]
