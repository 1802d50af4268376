"""Plain reference of a proxy spec: what its answer is, from the spec alone.

A proxy spec is a DAG of dwarf components over flat buffers.  Its answer
for one setting of the dynamic parameters and one PRNG key is a scalar:

* every source node ``s`` (in sorted name order, position ``i``) holds
  ``normal(fold_in(key, i), (n,))`` float32 values;
* edges run in list order.  An edge reads its first input fitted to its
  ``data_size`` (tiled or cut), applies its component ``weight`` times,
  the ``r``-th time with the key ``fold_in(key, 10000 + 131 * edge + r)``,
  fitting each output back to ``data_size``, and adds its output to what
  its destination node already holds (fitted to that node's length);
* the answer is the sum of every element of the sink node.

Each component's arithmetic lives in a module of this package named after
the component (``bench/reference/<component>.py``, one ``apply(x, p,
key)`` each), found by name.  Nothing here imports the program: the
reference runs from the JSON spec alone.  Weights unroll in Python, one
jitted call per repeat, so no loop of the program's shape is shared.

``Reference(spec)`` computes in float32, with the matrix products at
the precision the configuration states (``precision``; ``default`` is
the backend's, as the program leaves its own: one bfloat16 pass with
float32 sums on a TPU, exact on a CPU), and sums the sink in float64 on
the host.  The control is the same reference one precision lower:
``precision="high"`` (three bfloat16 passes per product) where the
configuration states float32 at ``highest``, or ``dtype="bfloat16"``
(every buffer and operation in bfloat16, the sink summed pairwise in
bfloat16 as the program sums its own in float32) where it states plain
float32.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: the rounding every spec edge goes through before it runs
MAX_DATA = 1 << 26


def rounded(edge: Dict[str, Any]) -> Dict[str, Any]:
    """The edge's sizes and weight as the spec format makes them legal:
    ``data_size`` in [256, 2**26], ``chunk_size`` a multiple of 8 in
    [8, data_size], ``data_size`` a multiple of the chunk."""
    data = int(max(256, min(int(edge["data_size"]), MAX_DATA)))
    chunk = int(max(8, min(int(edge["chunk_size"]), data)))
    chunk = max(8, (chunk // 8) * 8)
    weight = int(round(max(0.0, min(float(edge.get("weight", 1)), 128.0))))
    data = max(chunk, (data // chunk) * chunk)
    return {"data_size": data, "chunk_size": chunk, "weight": weight,
            "extra": dict(edge.get("extra", {}))}


def fit(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """The flat buffer cut or tiled to ``n`` elements."""
    x = x.reshape(-1)
    if x.shape[0] >= n:
        return x[:n]
    return jnp.tile(x, -(-n // x.shape[0]))[:n]


def rows(x: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """The buffer as ``(rows, chunk)``: whole chunks, at least one."""
    n = max((x.shape[0] // chunk) * chunk, chunk)
    return fit(x, n).reshape(-1, chunk)


def as_u32(x: jnp.ndarray) -> jnp.ndarray:
    """The float32 bit pattern of each element."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)


def component(name: str):
    """The reference module of a component, by its registry name."""
    return importlib.import_module(f"{__name__}.{name}")


def pairwise_sum(v: jnp.ndarray) -> jnp.ndarray:
    """Sum in the buffer's own dtype, halves added elementwise until one
    element is left (zero-padded to a power of two)."""
    v = v.reshape(-1)
    n = v.shape[0]
    v = jnp.pad(v, (0, (1 << max(n - 1, 0).bit_length()) - n))
    while v.shape[0] > 1:
        half = v.shape[0] // 2
        v = v[:half] + v[half:]
    return v[0]


def source(key: jax.Array, index: int, n: int, dtype) -> jnp.ndarray:
    return jax.random.normal(jax.random.fold_in(key, index), (n,),
                             jnp.float32).astype(dtype)


class Reference:
    """The spec's answer, computed plainly (see the module docstring)."""

    def __init__(self, spec: Dict[str, Any], dtype="float32",
                 precision: str = "default"):
        self.spec = spec
        self.dtype = jnp.dtype(dtype)
        self.precision = precision
        self.edges = [rounded(e) for e in spec["edges"]]
        self._jit: Dict[Tuple, Any] = {}
        self._pairwise = jax.jit(pairwise_sum)

    def _repeat_fn(self, ei: int, extra: Tuple):
        """Jitted single repeat of edge ``ei`` with these extras."""
        key = (ei, extra)
        if key not in self._jit:
            e = self.spec["edges"][ei]
            p = dict(self.edges[ei], extra=dict(extra))
            mod = component(e["component"])
            size = p["data_size"]

            def one(x, k):
                with jax.default_matmul_precision(self.precision):
                    return fit(mod.apply(fit(x, size), p, k).reshape(-1),
                               size)

            self._jit[key] = jax.jit(one)
        return self._jit[key]

    def sink(self, dyn: Sequence[Dict[str, Any]], key: jax.Array
             ) -> jnp.ndarray:
        """The sink node's buffer for per-edge dynamic values ``dyn``
        (``{"weight": w, <extra>: v}`` per edge, as plain numbers)."""
        nodes: Dict[str, jnp.ndarray] = {}
        for i, (name, n) in enumerate(sorted(self.spec["sources"].items())):
            nodes[name] = source(key, i, int(n), self.dtype)
        for ei, (e, p) in enumerate(zip(self.spec["edges"], self.edges)):
            d = dict(dyn[ei]) if ei < len(dyn) else {}
            weight = int(round(float(d.pop("weight", p["weight"]))))
            extra = {**p["extra"], **{k: float(v) for k, v in d.items()}}
            size = p["data_size"]
            xs = [nodes[s] for s in e["src"]]
            x = xs[0] if len(xs) == 1 else jnp.concatenate(
                [fit(v, size) for v in xs])
            out = fit(x, size)
            fn = self._repeat_fn(ei, tuple(sorted(extra.items())))
            for r in range(max(weight, 0)):
                out = fn(out, jax.random.fold_in(key, 10_000 + 131 * ei + r))
            prev = nodes.get(e["dst"])
            nodes[e["dst"]] = out if prev is None else \
                prev + fit(out, prev.shape[0])
        return nodes[self.spec["sink"]]

    def answer(self, dyn: Sequence[Dict[str, Any]], key: jax.Array
               ) -> Tuple[float, float]:
        """``(answer, l1)``: the sink's sum and the sum of its magnitudes.

        In float32 the sum is taken in float64 on the host; in bfloat16
        (a control) it is the pairwise sum in bfloat16, as the program
        forms its own answer in float32."""
        out = self.sink(dyn, key)
        host = np.asarray(out.astype(jnp.float32), np.float64)
        l1 = float(np.abs(host).sum())
        if self.dtype == jnp.float32:
            return float(host.sum()), l1
        return float(self._pairwise(out).astype(jnp.float32)), l1


def dyn_of(names: List[Tuple[int, str]], row: Sequence[float],
           n_edges: int) -> List[Dict[str, float]]:
    """Per-edge dynamic values from ``(edge, field)`` names and a row."""
    out: List[Dict[str, float]] = [dict() for _ in range(n_edges)]
    for (ei, field), v in zip(names, row):
        out[ei][field] = float(v)
    return out
