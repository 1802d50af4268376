"""Row-wise top-k Pallas kernel — sort dwarf / MoE router hot spot.

Each program owns a (bm, N) row tile in VMEM and extracts k maxima with
k (max, mask) sweeps — vector-unit only, no data-dependent control flow,
so it lowers to TPU without a sort network.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -3.4e38


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)                  # (bm, N)
    bm, n = x.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 1)
    for j in range(k):
        m = x.max(axis=1)                               # (bm,)
        # first column achieving the max
        hit = (x == m[:, None])
        first = jnp.min(jnp.where(hit, cols, n), axis=1)
        vals_ref[:, j] = m.astype(vals_ref.dtype)
        idx_ref[:, j] = first.astype(jnp.int32)
        x = jnp.where(cols == first[:, None], NEG_INF, x)


def topk_kernel(x: jnp.ndarray, k: int, *, block_m: int = 256,
                interpret: bool = True):
    M, N = x.shape
    bm = min(block_m, M)
    assert M % bm == 0
    kern = functools.partial(_topk_kernel, k=k)
    return pl.pallas_call(
        kern,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((bm, k), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((M, k), x.dtype),
                   jax.ShapeDtypeStruct((M, k), jnp.int32)),
        name="topk",
        interpret=interpret,
    )(x)
