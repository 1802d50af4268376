"""Row-wise bitonic sort network Pallas kernel — sort dwarf hot spot.

Each program owns a (bm, N) row tile in VMEM and sorts every row
ascending with a bitonic network: log2(N)·(log2(N)+1)/2 compare-exchange
stages, each two lane rotations + min/max + select on the 2-D tile.  The
network is data-independent — no gathers, no reshapes, no data-dependent
control flow — so it lowers to the TPU vector and cross-lane units
directly, where XLA's variadic ``sort`` falls back to a serial
comparator loop.

The network body (:func:`bitonic_sort_rows`) is pure jnp over values, not
refs, so the exact same comparator sequence also serves as the sort
segment body inside the :mod:`repro.kernels.megakernel` fused-stage
kernel (a nested ``pallas_call`` is not expressible there).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: row lengths the network accepts: a power of two, at least one full
#: 128-lane vector register row (callers pad shorter rows)
MIN_ROW = 128

#: elements per row tile — bounds the VMEM footprint of one program's
#: network temporaries (the in/out tiles are double-buffered besides)
TILE_ELEMS = 1 << 15


def next_pow2(n: int) -> int:
    """Smallest power of two >= n."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def bitonic_sort_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Sort each row of a (rows, n) array ascending; n must be a power of
    two (callers pad with the dtype's maximum so pads sink to the tail).

    Stage (k, j) pairs element i with i^j: the partner is the row rotated
    by ``n - j`` (element i+j) where bit j of i is clear, and rotated by
    ``j`` (element i-j) where it is set.  The rotation direction is read
    off a rotated lane iota rather than assumed, so the network is right
    under either rotation convention.  Element i keeps the min of the pair
    when "bit j clear" agrees with the ascending direction ``(i & k) == 0``
    and the max otherwise — min/max are symmetric, so the values equal the
    reshape-and-stack formulation's exactly.
    """
    rows, n = x.shape
    if n & (n - 1):
        raise ValueError(f"bitonic_sort_rows needs a power-of-two row "
                         f"length, got {n}")
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            fwd = pltpu.roll(x, n - j, 1)
            bwd = pltpu.roll(x, j, 1)
            fwd_is_partner = pltpu.roll(lane, n - j, 1) == (lane ^ j)
            partner = jnp.where(fwd_is_partner, fwd, bwd)
            low = (lane & j) == 0
            keep_min = low == ((lane & k) == 0)
            x = jnp.where(keep_min, jnp.minimum(x, partner),
                          jnp.maximum(x, partner))
            j //= 2
        k *= 2
    return x


def _sort_kernel(x_ref, o_ref):
    o_ref[...] = bitonic_sort_rows(x_ref[...])


def row_block(m: int, n: int, block_m: int) -> int:
    """Rows per program: at most ``block_m`` and :data:`TILE_ELEMS`
    elements, a multiple of 8 sublanes unless it is all ``m`` rows."""
    bm = min(block_m, max(8, TILE_ELEMS // n))
    return m if m <= bm else bm - bm % 8


def sort_net_kernel(x: jnp.ndarray, *, block_m: int = 256,
                    interpret: bool = True) -> jnp.ndarray:
    M, N = x.shape
    bm = row_block(M, N, block_m)
    if M % bm:
        raise ValueError(f"{M} rows do not split into {bm}-row blocks")
    return pl.pallas_call(
        _sort_kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(x)
