"""Graph computation dwarf — construction, traversal, degree counting.

Irregular gather/scatter-dominant access patterns (the paper singles graph
computations out for exactly this).  The order-free scatters onto a small
vertex table (degree counts, the hop's max) go through the dense blocked
reductions of :mod:`.statistic`, which give the scatter's bits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import ComponentParams, DwarfComponent, as_u32, register
from .statistic import grouped_counts, segment_max


def _edges_from_buffer(x: jnp.ndarray, n_vertices: int):
    u = as_u32(x)
    n2 = (u.shape[0] // 2) * 2
    src = (u[:n2:2] % jnp.uint32(n_vertices)).astype(jnp.int32)
    dst = (u[1:n2:2] % jnp.uint32(n_vertices)).astype(jnp.int32)
    return src, dst


@register
class GraphConstruction(DwarfComponent):
    """Edge list -> degree arrays (out/in degree count of nodes)."""

    name = "graph_construction"
    dwarf = "graph"

    def apply(self, x: jnp.ndarray, p: ComponentParams, rng: jax.Array):
        v = int(p.extra.get("vertices", max(64, x.shape[0] // 8)))
        src, dst = _edges_from_buffer(x, v)
        out_deg = grouped_counts(src, v)
        in_deg = grouped_counts(dst, v)
        gathered = out_deg[src] + in_deg[dst]       # gather back along edges
        return gathered


@register
class GraphTraversal(DwarfComponent):
    """Frontier-propagation BFS sweep (hops x scatter-max + gather)."""

    name = "graph_traversal"
    dwarf = "graph"

    def apply(self, x: jnp.ndarray, p: ComponentParams, rng: jax.Array):
        v = int(p.extra.get("vertices", max(64, x.shape[0] // 8)))
        hops = int(p.extra.get("hops", 4))
        src, dst = _edges_from_buffer(x, v)
        frontier = jnp.zeros((v,), jnp.float32).at[0].set(1.0)

        def hop(f, _):
            nxt = segment_max(f[src], dst, v)
            return jnp.maximum(f, nxt), ()

        frontier, _ = jax.lax.scan(hop, frontier, None, length=hops)
        return frontier[dst % v]


@register
class SpMV(DwarfComponent):
    """Sparse matrix-vector product y[dst] += x[src]/deg[src] (PageRank)."""

    name = "spmv"
    dwarf = "graph"

    def apply(self, x: jnp.ndarray, p: ComponentParams, rng: jax.Array):
        v = int(p.extra.get("vertices", max(64, x.shape[0] // 8)))
        src, dst = _edges_from_buffer(x, v)
        deg = grouped_counts(src, v)
        rank = jnp.full((v,), 1.0 / v)
        contrib = rank[src] / jnp.maximum(deg[src], 1.0)
        # the sum keeps the scatter: a float sum depends on its order
        new_rank = jnp.zeros((v,), jnp.float32).at[dst].add(contrib)
        return new_rank[dst]
