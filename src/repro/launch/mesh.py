"""Production mesh factory.

Function (not module constant) so importing never touches jax device state.
Single pod: 16x16 = 256 chips (v5e pod), axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model); 'pod' is a pure
data-parallel axis (weights replicated across pods, gradients all-reduced
across the slower inter-pod links once per step).
"""

from __future__ import annotations

import jax


def _axis_type_kw(n: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_type_kw(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return jax.make_mesh(
        (data, model), ("data", "model"), **_axis_type_kw(2))
