"""Public wrapper with shape padding for the tiled matmul kernel.

Backend-dispatched through :mod:`repro.kernels.dispatch`: the resolved
backend / interpret flag are decided per call outside jit, so
``REPRO_BACKEND=xla`` and the circuit breaker's ``forced_backend`` degrade
actually turn the kernel off, and the resolved values key the jit cache.
"""
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..dispatch import default_interpret, resolve_backend
from .kernel import matmul_kernel


def _tile(block: int, dim: int, align: int) -> int:
    """A block edge the TPU lowering accepts: the whole dimension when the
    requested block covers it, else the block rounded up to a multiple of
    ``align`` (8 sublanes for rows, 128 lanes for columns)."""
    if dim <= block:
        return dim
    t = -(-block // align) * align
    return dim if t >= dim else t


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def _matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, *, block_m: int,
                   block_n: int, block_k: int, interpret: bool) -> jnp.ndarray:
    M, K = a.shape
    _, N = b.shape
    # K is a's lane dim and b's sublane dim, N is a lane dim
    bm, bn, bk = (_tile(block_m, M, 8), _tile(block_n, N, 128),
                  _tile(block_k, K, 128))
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    ap = jnp.pad(a, ((0, pm), (0, pk))) if (pm or pk) else a
    bp = jnp.pad(b, ((0, pk), (0, pn))) if (pk or pn) else b
    out = matmul_kernel(ap, bp, block_m=bm, block_n=bn, block_k=bk,
                        interpret=interpret)
    return out[:M, :N]


@jax.jit
def _matmul_xla(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    # f32 accumulation like the kernel's scratch; out dtype matches the kernel
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def matmul(a: jnp.ndarray, b: jnp.ndarray, *, block_m: int = 128,
           block_n: int = 128, block_k: int = 128,
           interpret: Optional[bool] = None,
           backend: Optional[str] = None) -> jnp.ndarray:
    if resolve_backend(backend) != "pallas":
        return _matmul_xla(a, b)
    if interpret is None:
        interpret = default_interpret()
    return _matmul_pallas(a, b, block_m=block_m, block_n=block_n,
                          block_k=block_k, interpret=interpret)
