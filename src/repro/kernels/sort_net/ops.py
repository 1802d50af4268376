"""jit'd wrapper for the bitonic sort network (row + length padding)."""
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..dispatch import default_interpret
from .kernel import MIN_ROW, next_pow2, row_block, sort_net_kernel


def _pad_max(dtype) -> jnp.ndarray:
    """The dtype's maximum — ascending sort pushes pads past every real
    element, so slicing the first N columns recovers the sorted row."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def sort_rows(x: jnp.ndarray, *, block_m: int = 256,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sort each row of a (M, N) array ascending via the bitonic network;
    any M and N (rows pad to the block multiple, lengths to the next
    power of two and at least :data:`MIN_ROW` lanes)."""
    if interpret is None:
        interpret = default_interpret()
    M, N = x.shape
    pn = max(next_pow2(N), MIN_ROW) - N
    bm = row_block(M, N + pn, block_m)
    pm = (-M) % bm
    xp = x
    if pn:
        xp = jnp.concatenate(
            [xp, jnp.full((M, pn), _pad_max(x.dtype), x.dtype)], axis=1)
    if pm:
        xp = jnp.concatenate(
            [xp, jnp.full((pm, xp.shape[1]), _pad_max(x.dtype), x.dtype)])
    out = sort_net_kernel(xp, block_m=bm, interpret=interpret)
    return out[:M, :N]
