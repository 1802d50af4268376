"""murmur3-finalizer hash Pallas kernel — the logic dwarf's bit-ops hot spot.

Pure VPU integer ops (xor, shifts, multiplies) over 2-D VMEM tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hash_kernel(x_ref, o_ref, *, rounds: int):
    u = x_ref[...]
    for _ in range(rounds):
        u = u ^ (u >> 16)
        u = u * jnp.uint32(0x85EBCA6B)
        u = u ^ (u >> 13)
        u = u * jnp.uint32(0xC2B2AE35)
        u = u ^ (u >> 16)
    o_ref[...] = u


def hash_mix_kernel(x: jnp.ndarray, *, rounds: int = 2, block: int = 1024,
                    interpret: bool = True) -> jnp.ndarray:
    M, N = x.shape
    bm = min(block, M)
    assert M % bm == 0
    kern = functools.partial(_hash_kernel, rounds=rounds)
    return pl.pallas_call(
        kern,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.uint32),
        name="hash_mix",
        interpret=interpret,
    )(x)
