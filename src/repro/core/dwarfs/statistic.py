"""Basic statistic computation dwarf — count, average, histogram, probability."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .base import (ComponentParams, DwarfComponent, as_chunks, as_u32,
                   mix_u32, register)


#: the largest static group count whose counts (and segment maxima) take
#: the dense reduction; above it the scatter, whose cost does not grow
#: with the group count, is cheaper.  Set from sweeps of one
#: ``grouped_count`` repeat at 2**22 and of the counts and the maximum
#: at 2**21 ids on a TPU v5e (PERF.md §5, §6): the counts cross over
#: between 4096 and 8192 groups, the maximum above 8192.
DENSE_GROUPS_MAX = 4096

#: lowering-time counters: how many grouped counts were traced through the
#: dense reduction and how many through the scatter
GROUPED_STATS = {"dense": 0, "scatter": 0}

#: lowering-time counters of :func:`segment_max`, kept apart from
#: :data:`GROUPED_STATS`
SEGMENT_MAX_STATS = {"dense": 0, "scatter": 0}

#: group-by-element compares per loop step of a dense reduction: bounds
#: the step's ``[groups, block]`` temporary where it is materialised (the
#: CPU) and keeps the loop short where it is fused (a TPU: 8 steps for
#: 16 groups at 2**22)
_STEP_COMPARES = 1 << 23


def grouped_stats() -> Dict[str, int]:
    """How many grouped counts were traced through each path, ``dense``
    and ``scatter`` (counted per trace, not per call)."""
    return dict(GROUPED_STATS)


def reset_grouped_stats() -> None:
    """Zero the grouped-path counters."""
    for k in GROUPED_STATS:
        GROUPED_STATS[k] = 0


def segment_max_stats() -> Dict[str, int]:
    """How many segment maxima were traced through each path, ``dense``
    and ``scatter`` (counted per trace, not per call)."""
    return dict(SEGMENT_MAX_STATS)


def reset_segment_max_stats() -> None:
    """Zero the segment-max path counters."""
    for k in SEGMENT_MAX_STATS:
        SEGMENT_MAX_STATS[k] = 0


def _dense_reduce(gid, groups, init, step, *values):
    """Fold ``step(acc, ids, *value_blocks)`` over the flat group ids
    ``gid`` a block of ``ids`` at a time, from ``init``: ``step``
    compares the block with every group.  Padded ids match no group, so
    padded values (0) reach no group."""
    n = gid.shape[0]
    block = min(n, _STEP_COMPARES // groups)
    steps = -(-n // block)

    def blocks(a, fill):
        return jnp.pad(a, (0, steps * block - n),
                       constant_values=fill).reshape(steps, block)

    out, _ = jax.lax.scan(lambda acc, blk: (step(acc, *blk), None), init,
                          (blocks(gid, groups),
                           *(blocks(v, 0) for v in values)))
    return out


def grouped_counts(gid: jnp.ndarray, groups: int) -> jnp.ndarray:
    """How many of the flat group ids ``gid`` fall in each group, float32
    ``[groups]``.

    A scatter-add onto a few bins serialises on its conflicting updates.
    Up to :data:`DENSE_GROUPS_MAX` groups the ids are compared with every
    group instead, a block at a time, and the hits summed in int32:
    exact, so equal to the scatter's float32 counts below 2**24 and free
    of any summation order.  Above the threshold the dense cost,
    ``groups`` compares per element, overtakes the scatter's.
    """
    if groups > DENSE_GROUPS_MAX:
        GROUPED_STATS["scatter"] += 1
        return jax.ops.segment_sum(jnp.ones(gid.shape, jnp.float32), gid,
                                   num_segments=groups)
    GROUPED_STATS["dense"] += 1
    ids = jnp.arange(groups, dtype=gid.dtype)[:, None]
    counts = _dense_reduce(
        gid, groups, jnp.zeros((groups,), jnp.int32),
        lambda acc, g: acc + jnp.sum(g[None, :] == ids, axis=1,
                                     dtype=jnp.int32))
    return counts.astype(jnp.float32)


def segment_max(values: jnp.ndarray, gid: jnp.ndarray,
                groups: int) -> jnp.ndarray:
    """The largest of ``values`` in each of ``groups`` segments and 0,
    ``[groups]``: ``jnp.zeros((groups,)).at[gid].max(values)``.

    Up to :data:`DENSE_GROUPS_MAX` groups each block of ids is compared
    with every group, the value or 0 selected, and the maximum taken
    over the block.  A maximum rounds nothing, so the result is the
    scatter's in any order (for values free of NaN and of -0.0).
    """
    if groups > DENSE_GROUPS_MAX:
        SEGMENT_MAX_STATS["scatter"] += 1
        return jnp.zeros((groups,), values.dtype).at[gid].max(values)
    SEGMENT_MAX_STATS["dense"] += 1
    # the block is compared as a column with the groups as a row, and
    # the maximum taken down the columns: XLA's CPU backend takes a float
    # maximum along rows some five times slower
    ids = jnp.arange(groups, dtype=gid.dtype)[None, :]
    return _dense_reduce(
        gid, groups, jnp.zeros((groups,), values.dtype),
        lambda acc, g, v: jnp.maximum(acc, jnp.max(
            jnp.where(g[:, None] == ids, v[:, None], 0), axis=0)),
        values)


@register
class CountAverage(DwarfComponent):
    """Per-chunk count/mean/variance (cluster count & average, Kmeans)."""

    name = "count_average"
    dwarf = "statistic"

    def apply(self, x: jnp.ndarray, p: ComponentParams, rng: jax.Array):
        rows = as_chunks(x, p)
        mean = rows.mean(axis=1, keepdims=True)
        var = rows.var(axis=1, keepdims=True)
        return (rows - mean) / jnp.sqrt(var + 1e-6)


@register
class Histogram(DwarfComponent):
    """Hash-bucketize + bincount (word-count / TF-IDF style counting).

    The bucket index is derived through murmur3 avalanche rounds
    (``mix_rounds``, default 1) — the hash hot spot dispatches to the
    ``kernels.hash_mix`` Pallas kernel on accelerator backends.
    """

    name = "histogram"
    dwarf = "statistic"

    dynamic_extras = ("mix_rounds",)
    pallas_static = ("mix_rounds",)
    pallas_capable = True

    def apply(self, x: jnp.ndarray, p: ComponentParams, rng: jax.Array):
        bins = int(p.extra.get("bins", 256))
        u = mix_u32(as_u32(x), p.extra.get("mix_rounds", 1),
                    backend=p.extra.get("backend"))
        idx = (u % jnp.uint32(bins)).astype(jnp.int32)
        return grouped_counts(idx, bins)[idx] * (1.0 / x.shape[0])


@register
class ProbabilityStats(DwarfComponent):
    """Softmax-normalized probabilities + entropy (naive-bayes style)."""

    name = "probability"
    dwarf = "statistic"

    def apply(self, x: jnp.ndarray, p: ComponentParams, rng: jax.Array):
        rows = as_chunks(x, p)
        logp = jax.nn.log_softmax(rows, axis=1)
        ent = -jnp.sum(jnp.exp(logp) * logp, axis=1, keepdims=True)
        return logp + ent


@register
class DegreeCount(DwarfComponent):
    """Each value minus the mean of its group (out/in degree counting):
    sums by segment-sum, counts by :func:`grouped_counts`; the
    hash-derived group id dispatches like :class:`Histogram`."""

    name = "grouped_count"
    dwarf = "statistic"

    dynamic_extras = ("mix_rounds",)
    pallas_static = ("mix_rounds",)
    pallas_capable = True

    def apply(self, x: jnp.ndarray, p: ComponentParams, rng: jax.Array):
        groups = int(p.extra.get("groups", 128))
        u = mix_u32(as_u32(x), p.extra.get("mix_rounds", 1),
                    backend=p.extra.get("backend"))
        gid = (u % jnp.uint32(groups)).astype(jnp.int32)
        # the sums keep the scatter: a dense sum adds in another order,
        # and on groups whose mean is far from zero its float32 rounding
        # moves the answer by more than bench/reference, which adds in
        # the scatter's order, allows
        sums = jax.ops.segment_sum(x, gid, num_segments=groups)
        means = sums / jnp.maximum(grouped_counts(gid, groups), 1.0)
        return x - means[gid]
