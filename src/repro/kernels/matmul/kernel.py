"""Tiled matmul Pallas TPU kernel — the matrix dwarf's MXU hot spot.

(bm, bn, bk) VMEM tiles, fp32 accumulator scratch, K as the innermost
sequential grid axis.  MXU-aligned defaults (128 multiples).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_kernel(a: jnp.ndarray, b: jnp.ndarray, *, block_m: int = 128,
                  block_n: int = 128, block_k: int = 128,
                  interpret: bool = True) -> jnp.ndarray:
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    return pl.pallas_call(
        _mm_kernel,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="matmul",
        interpret=interpret,
    )(a, b)
