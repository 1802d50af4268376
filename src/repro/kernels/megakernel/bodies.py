"""Registered in-kernel segment bodies for the FusedStage megakernel.

A *body* is a pure ``(rows, lane) f32 -> (rows, lane) f32`` function over
the stage's carry (laid out by :func:`mega_lane`) replicating exactly one
repeat of a dwarf component — the
same value :func:`repro.core.dag._edge_out`'s loop body produces — as
plain jnp ops traceable *inside* a Pallas kernel (no nested
``pallas_call``, no rng).  Only components whose ``apply`` ignores the
rng and whose per-repeat output is value-identical to the XLA lowering
may register here; that is what makes the one-kernel stage bit-identical
to the ``fori_loop`` + ``lax.switch`` path.

:func:`mega_body` returns ``None`` when a component has no registered
body **or** its params break the identity contract (a chunk-row body
whose ``parallelism`` lane split in ``DwarfComponent.__call__`` would
re-tile lanes, a chunk that is not a whole number of 128-lane vector rows,
a dynamic kernel-static extra) — ``core/schedule.py`` then keeps the stage
on the switch path.  Bodies use only elementwise ops, lane rotations and
row reductions on the 2-D tile: nothing the TPU compiler must reshape.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

MegaBody = Callable[[jnp.ndarray], jnp.ndarray]

#: the TPU vector register's lane count: chunk-row bodies need whole
#: vector rows, so a chunk must be a multiple of it
LANES = 128

#: sentinel the top-k max-sweep masks claimed maxima with (must match
#: repro.kernels.topk.kernel.NEG_INF for bit-identity with the kernel)
_NEG_INF = -3.4e38


def _lane_split_clean(p) -> bool:
    """Mirror ``DwarfComponent.__call__``'s parallelism lane split: a
    chunk-row body is safe iff the split either does not engage or cuts
    the buffer into whole chunk rows (then vmap-over-lanes ≡ one rowwise
    pass over the full buffer)."""
    if p.parallelism <= 1:
        return True
    size = p.data_size
    lanes = min(p.parallelism, max(1, size // max(p.chunk_size, 8)))
    if lanes <= 1 or size % lanes != 0:
        return True                     # __call__ falls through to apply()
    return (size // lanes) % p.chunk_size == 0


def _static_int(v) -> Optional[int]:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return max(int(round(float(v))), 0)


def _body_hash(p) -> Optional[MegaBody]:
    rounds = _static_int(p.extra.get("rounds", 4))
    if rounds is None:
        return None

    def body(x: jnp.ndarray) -> jnp.ndarray:
        from ...core.dwarfs.base import _mix32_round, as_u32
        u = as_u32(x)
        for _ in range(rounds):
            u = _mix32_round(u)
        # u32_to_f32, with the 24-bit value cast through i32: the TPU
        # compiler has no u32 -> f32 conversion, and both are exact here
        return (u >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))

    return body


def _body_top_k(p) -> Optional[MegaBody]:
    c = p.chunk_size
    k = _static_int(p.extra.get("k", 32))
    if k is None or k < 1:
        return None
    k = min(k, c)

    def body(x: jnp.ndarray) -> jnp.ndarray:
        cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        slot = cols % k            # column j repeats the (j % k)-th max
        out = jnp.zeros_like(x)
        for t in range(k):        # the topk kernel's (max, mask) sweep
            m = x.max(axis=1, keepdims=True)
            first = jnp.min(jnp.where(x == m, cols, c), axis=1,
                            keepdims=True)
            out = jnp.where(slot == t, m, out)
            x = jnp.where(cols == first, _NEG_INF, x)
        return out

    return body


def _body_full_sort(p) -> Optional[MegaBody]:
    # quick_sort sorts every chunk row; merge_sort merges two sorted
    # halves, which equals the full row sort whenever the chunk is even.
    # The network runs on the chunk row in place, so the chunk must be a
    # power of two (no pad columns to concatenate inside the kernel)
    from ..sort_net.kernel import bitonic_sort_rows
    c = p.chunk_size
    if c & (c - 1):
        return None
    return bitonic_sort_rows


def _body_min_max(p) -> Optional[MegaBody]:
    c = p.chunk_size

    def body(x: jnp.ndarray) -> jnp.ndarray:
        mn = x.min(axis=1, keepdims=True)
        mx = x.max(axis=1, keepdims=True)
        return (x - mn) / jnp.maximum(mx - mn, 1e-6)

    return body


#: component name -> body factory.  hash is elementwise; the rest view
#: the carry as (rows, chunk) and must survive the lane-split check.
_FACTORIES = {
    "hash": _body_hash,
    "top_k": _body_top_k,
    "quick_sort": _body_full_sort,
    "merge_sort": _body_full_sort,
    "min_max": _body_min_max,
}
_CHUNK_ROW = frozenset(("top_k", "quick_sort", "merge_sort", "min_max"))


def mega_body(component: str, p) -> Optional[MegaBody]:
    """The registered segment body for ``component`` under (rounded)
    params ``p``, or ``None`` when no bit-identical body exists."""
    factory = _FACTORIES.get(component)
    if factory is None:
        return None
    p = p.rounded()
    if p.data_size % p.chunk_size:          # rounded() guarantees this,
        return None                         # but never trust a caller
    if component in _CHUNK_ROW and (p.chunk_size % LANES
                                    or not _lane_split_clean(p)):
        return None
    return factory(p)


def mega_capable(component: str, p) -> bool:
    return mega_body(component, p) is not None


def mega_lane(members: Sequence[Tuple[str, object]]) -> Optional[int]:
    """Row width of a stage carry's ``(rows, lane)`` VMEM layout, or
    ``None`` when no single layout serves every member: the chunk of the
    chunk-row members (they must agree — each body's row is its
    component's chunk), else one vector register row.  ``members`` are
    ``(component, params)`` pairs in stage order."""
    chunks = {p.rounded().chunk_size for comp, p in members
              if comp in _CHUNK_ROW}
    if len(chunks) > 1:
        return None
    lane = chunks.pop() if chunks else LANES
    size = members[-1][1].rounded().data_size
    return lane if size % lane == 0 else None
