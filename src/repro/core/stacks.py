"""Deprecated software-stack entry points (paper §2.2.2).

The four ad-hoc functions this module used to define have been redesigned
into the unified Stack protocol in :mod:`repro.api.stack`::

    from repro.api import get_stack
    report = get_stack("hadoop").run(proxy)      # -> RunReport

These shims keep the old ``(result, io_bytes)`` signatures working and
delegate to the new implementations.
"""

from __future__ import annotations

import warnings
from typing import Callable, Tuple

import jax
from jax.sharding import PartitionSpec as P

from ..api.stack import (HadoopStack, MPIStack, OpenMPStack,  # noqa: F401
                         SparkStack, get_stack)


def _warn(name: str) -> None:
    warnings.warn(
        f"repro.core.stacks.{name}() is deprecated; use "
        f"repro.api.get_stack({name!r}).run(...)",
        DeprecationWarning, stacklevel=3)


def openmp(fn: Callable, *args) -> Tuple[jax.Array, float]:
    _warn("openmp")
    r = get_stack("openmp").run(fn, *args)
    return r.result, r.io_bytes


def mpi(fn: Callable, mesh: jax.sharding.Mesh, axis: str, *args,
        out_specs=P()) -> Tuple[jax.Array, float]:
    """SPMD execution with the original semantics: per-shard inputs over
    ``axis`` and caller-controlled ``out_specs``.  (``MPIStack.run`` instead
    replicates inputs and all-reduces outputs — a different contract.)"""
    _warn("mpi")
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=P(axis),
                            out_specs=out_specs, check_vma=False)
    out = jax.jit(sharded)(*args)
    jax.block_until_ready(out)
    return out, 0.0


def spark(fn: Callable, mesh: jax.sharding.Mesh, axis: str, *args
          ) -> Tuple[jax.Array, float]:
    _warn("spark")
    r = SparkStack(mesh=mesh, axis=axis).run(fn, *args)
    return r.result, r.io_bytes


def hadoop(map_fn: Callable, reduce_fn: Callable, data: jax.Array,
           n_chunks: int = 8) -> Tuple[jax.Array, float]:
    _warn("hadoop")
    r = HadoopStack(n_chunks=n_chunks).map_reduce(map_fn, reduce_fn, data)
    return r.result, r.io_bytes
