"""Each value minus the mean of its group.  A value's group is its
float32 bit pattern through ``mix_rounds`` murmur3 finalizer rounds,
modulo ``groups``."""

import jax.numpy as jnp

from . import as_u32


def mix(u, rounds):
    for _ in range(rounds):
        u = u ^ (u >> 16)
        u = u * jnp.uint32(0x85EBCA6B)
        u = u ^ (u >> 13)
        u = u * jnp.uint32(0xC2B2AE35)
        u = u ^ (u >> 16)
    return u


def apply(x, p, key):
    groups = int(p["extra"].get("groups", 128))
    rounds = max(int(round(p["extra"].get("mix_rounds", 1))), 0)
    gid = (mix(as_u32(x), rounds) % jnp.uint32(groups)).astype(jnp.int32)
    sums = jnp.zeros((groups,), x.dtype).at[gid].add(x)
    counts = jnp.zeros((groups,), x.dtype).at[gid].add(1)
    return x - (sums / jnp.maximum(counts, 1))[gid]
