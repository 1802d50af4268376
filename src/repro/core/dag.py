"""DAG-like combination of dwarf components (paper §2.1/§2.3).

A node represents an original or intermediate data set; an edge represents a
dwarf component applied with its own tunable parameters.  ``weight`` is the
component's contribution — realized as a repeat count, so doubling a weight
doubles that component's share of the proxy's cost channels (which is exactly
what the auto-tuner exploits).

Repeats execute as a ``jax.lax.fori_loop``, so graph size and compile time
are O(edges) — independent of the DAG's total weight.  Every edge's tunables
split into a **static structure** (component, the shape-affecting sizes —
:meth:`Edge.structure_key`) and a **dynamic param vector** (weight plus
shape-free extras — :meth:`ProxyDAG.dynamic_params`) that
:meth:`ProxyDAG.build_parametric` accepts as a jitted argument: stepping a
dynamic param re-executes the same compiled program, no retrace.

Every execution form lowers through one pipeline —
:func:`repro.core.schedule.lower` — which turns the DAG into an
:class:`~repro.core.schedule.ExecutionPlan` (ordered fused stages + the
population bucket schedule).  The historical ``build*`` methods remain as
thin shims over an *unfused* plan (legacy one-stage-per-edge semantics,
current params baked):

* :meth:`ProxyDAG.build` — one jit-able ``fn(rng) -> scalar`` with the
  current params baked in (fully analyzable HLO with ``known_trip_count``
  weights for the profiler).
* :meth:`ProxyDAG.build_parametric` — ``fn(rng, dyn) -> scalar``, the
  compile-once/run-many form the ``repro.core.engine`` cost model keys on
  ``structure_key()``.
* :meth:`ProxyDAG.build_population` — the vmapped candidate-batch form.
* :meth:`ProxyDAG.build_stages` / :meth:`ProxyDAG.build_stages_parametric`
  — deprecated per-edge staging; staged drivers consume
  ``ExecutionPlan.stages_parametric()`` (fused-stage granularity) instead.

The stacks (:mod:`repro.api.stack`) lower with the live fusion threshold
(``REPRO_FUSION_THRESHOLD``) and cache executables per plan structure key.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dwarfs import ComponentParams, get_component
from .dwarfs.base import fit_buffer


class StructureError(ValueError):
    """A DAG violates the structural invariants machine-generated
    structures must hold (see :meth:`ProxyDAG.validate_structure`)."""

#: dynamic fields passed as i32 (they become loop bounds); the rest are f32
_INT_DYNAMIC = {"weight", "rounds", "mix_rounds", "hops", "levels"}


def _json_scalar(v):
    """Coerce a param scalar to its JSON-native type (numpy ints/floats —
    a tuner-applied vector's dtype — are not json-serializable)."""
    if isinstance(v, bool) or isinstance(v, str):
        return v
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, (float, np.floating)):
        return float(v)
    return v


@dataclasses.dataclass
class Edge:
    component: str                 # registry name of the dwarf component
    src: Sequence[str]             # input node names (>=1, concatenated)
    dst: str                       # output node name
    params: ComponentParams = dataclasses.field(default_factory=ComponentParams)

    def to_json(self) -> Dict:
        p = self.params.rounded()
        return {
            "component": self.component, "src": list(self.src), "dst": self.dst,
            "data_size": int(p.data_size), "chunk_size": int(p.chunk_size),
            "parallelism": int(p.parallelism), "weight": int(p.weight),
            # machine-generated params (tuner vectors, mutations) may carry
            # numpy scalars; normalize to JSON-native types so the spec
            # round-trip is lossless for any structure, not just the
            # hand-written proxies
            "extra": {k: _json_scalar(v) for k, v in p.extra.items()},
        }

    @classmethod
    def from_json(cls, d: Dict) -> "Edge":
        return cls(d["component"], list(d["src"]), d["dst"],
                   ComponentParams(int(d.get("data_size", 1 << 14)),
                                   int(d.get("chunk_size", 256)),
                                   int(d.get("parallelism", 1)),
                                   int(round(float(d.get("weight", 1)))),
                                   dict(d.get("extra", {}))))

    # -- static / dynamic split ---------------------------------------------

    def dynamic_fields(self) -> Tuple[str, ...]:
        """Tunables steppable without a retrace: ``weight`` + the
        component's declared shape-free extras present on this edge."""
        return get_component(self.component).dynamic_fields(
            self.params.rounded())

    def structure_key(self) -> Tuple:
        """Hashable key of everything that affects this edge's compiled
        shape/program: component, shape-affecting sizes, static extras,
        the *names* (not values) of its dynamic params, and — for
        components with a Pallas fast path — the *resolved* backend and
        interpret mode, so a ``REPRO_BACKEND`` / ``REPRO_PALLAS_INTERPRET``
        change never hits an executable compiled for the other setting."""
        p = self.params.rounded()
        comp = get_component(self.component)
        dyn = set(self.dynamic_fields())
        static_extra = tuple(sorted(
            (k, v) for k, v in p.extra.items() if k not in dyn))
        backend = None
        if comp.pallas_capable:
            from ..kernels.dispatch import default_interpret
            backend = ("pallas", default_interpret()) \
                if comp.uses_pallas(p) else "xla"
        return (self.component, p.data_size, p.chunk_size, p.parallelism,
                static_extra, tuple(sorted(dyn - {"weight"})), backend)

    def dynamic_values(self) -> Dict[str, jnp.ndarray]:
        """Current dynamic param values as jit-argument scalars."""
        p = self.params.rounded()
        out: Dict[str, jnp.ndarray] = {}
        for f in self.dynamic_fields():
            v = p.weight if f == "weight" else p.extra[f]
            if f in _INT_DYNAMIC:
                out[f] = jnp.asarray(int(round(float(v))), jnp.int32)
            else:
                out[f] = jnp.asarray(float(v), jnp.float32)
        return out


# -- shared edge semantics (build and build_stages must agree exactly) -------


def _init_sources(sources: Dict[str, int], rng: jax.Array
                  ) -> Dict[str, jnp.ndarray]:
    return {sname: jax.random.normal(jax.random.fold_in(rng, i),
                                     (int(n),), jnp.float32)
            for i, (sname, n) in enumerate(sorted(sources.items()))}


def _gather_inputs(e: Edge, xs: List[jnp.ndarray]) -> jnp.ndarray:
    return xs[0] if len(xs) == 1 else jnp.concatenate(
        [fit_buffer(v, e.params.data_size) for v in xs])


def _edge_out(e: Edge, ei: int, x: jnp.ndarray, rng: jax.Array,
              dyn: Optional[Dict[str, jnp.ndarray]] = None) -> jnp.ndarray:
    """Apply edge ``e`` — ``weight`` repeats as a ``fori_loop``.

    ``dyn`` (from :meth:`ProxyDAG.dynamic_params`) overrides the weight and
    shape-free extras with traced scalars; without it every value is baked
    in statically (the loop still has a constant ``known_trip_count``, so
    the HLO cost analyzer attributes repeats exactly while the jaxpr stays
    O(1) in the weight).
    """
    comp = get_component(e.component)
    p = e.params
    if dyn:
        extra_dyn = {k: v for k, v in dyn.items() if k != "weight"}
        if extra_dyn:
            p = p.replace(extra={**p.extra, **extra_dyn})
    w = dyn["weight"] if dyn and "weight" in dyn else p.weight
    with jax.named_scope(e.component):      # names the edge's device ops
        x0 = fit_buffer(x, p.data_size)
        if isinstance(w, int) and w == 0:    # tuner pruned this edge
            return x0

        def body(i, out):
            r = jax.random.fold_in(rng, 10_000 + 131 * ei + i)
            return fit_buffer(comp(out, p, r), p.data_size)

        return jax.lax.fori_loop(0, w, body, x0)


def _accumulate(prev: Optional[jnp.ndarray], out: jnp.ndarray) -> jnp.ndarray:
    return out if prev is None else prev + fit_buffer(out, prev.shape[0])


def _terminals(edges: List[Edge]) -> List[str]:
    produced = {e.dst for e in edges}
    consumed = {s for e in edges for s in e.src}
    return sorted(produced - consumed) or sorted(produced)


def _checksum(v: jnp.ndarray) -> jnp.ndarray:
    """Sum of every element, in a fixed pairwise order.

    On a TPU ``jnp.sum``'s float accumulation order follows the array's
    layout, so one lane reduced alone and the same lane inside a vmap of
    several give other bits.  Adding zero-padded halves elementwise fixes
    the order, so a population or serving lane equals its single run."""
    v = jnp.ravel(v)
    if not jnp.issubdtype(v.dtype, jnp.floating):
        return jnp.sum(v)                  # integer sums are exact
    n = v.shape[0]
    v = jnp.pad(v, (0, (1 << max(n - 1, 0).bit_length()) - n))
    while v.shape[0] > 1:
        half = v.shape[0] // 2
        v = v[:half] + v[half:]
    return v[0]


def _output(nodes: Dict[str, jnp.ndarray], sink: Optional[str],
            edges: List[Edge]) -> jnp.ndarray:
    """The DAG's scalar result: the sink's checksum, or the sum of every
    terminal node's checksum when there is no sink."""
    if sink is not None:
        return _checksum(nodes[sink])
    return sum(_checksum(nodes[t]) for t in _terminals(edges))


@dataclasses.dataclass
class ProxyDAG:
    """Executable DAG of weighted dwarf components."""

    name: str
    sources: Dict[str, int]        # source node -> element count
    edges: List[Edge]
    sink: Optional[str] = None     # node reduced to the scalar output

    # -- validation ----------------------------------------------------------

    def validate(self) -> None:
        known = set(self.sources)
        for e in self.edges:
            for s in e.src:
                if s not in known:
                    raise ValueError(
                        f"edge {e.component}: input node {s!r} not yet defined "
                        f"(DAG must be topologically ordered)")
            known.add(e.dst)
        if self.sink is not None and self.sink not in known:
            raise ValueError(f"sink {self.sink!r} not produced by any edge")

    def _rounded_edges(self) -> List[Edge]:
        return [dataclasses.replace(e, params=e.params.rounded())
                for e in self.edges]

    # -- static / dynamic split ---------------------------------------------

    def structure_key(self) -> Tuple:
        """Hashable key of the DAG's compiled structure: topology, sources,
        every edge's static structure.  Two DAGs with equal keys share one
        compiled executable — only their dynamic param vectors differ."""
        return (tuple(sorted(self.sources.items())),
                tuple((tuple(e.src), e.dst, e.structure_key())
                      for e in self.edges),
                self.sink)

    def dynamic_params(self) -> Tuple[Dict[str, jnp.ndarray], ...]:
        """Per-edge dynamic param pytree, the second argument of
        :meth:`build_parametric` — stepping any leaf value re-runs the
        cached executable without retracing."""
        return tuple(e.dynamic_values() for e in self.edges)

    def canonical_structure_key(self) -> Tuple:
        """:meth:`structure_key` made stable under isomorphic relabeling.

        Node names are replaced by canonical ids — sources by their
        sorted-name position (the same index :func:`_init_sources` folds
        into the rng), edge outputs by first-production order — so two
        DAGs that differ only in node names share one key.  Equal keys
        imply bit-identical computation: every rng fold is keyed on the
        edge index and the sorted source position, both of which the key
        preserves.  This is what the plan/executable caches key on, so
        machine-generated structures that are mere relabelings of an
        already-compiled structure never cost a second compile."""
        ids: Dict[str, Tuple] = {
            s: ("s", i) for i, s in enumerate(sorted(self.sources))}
        nxt = 0
        entries = []
        for e in self.edges:
            srcs = tuple(ids[s] for s in e.src)
            if e.dst not in ids:
                ids[e.dst] = ("n", nxt)
                nxt += 1
            entries.append((srcs, ids[e.dst], e.structure_key()))
        return (tuple(int(n) for _, n in sorted(self.sources.items())),
                tuple(entries),
                None if self.sink is None else ids.get(self.sink))

    # -- structural invariants (machine-generated structures) ---------------

    def contributing_mask(self) -> List[bool]:
        """``mask[i]`` — does edge ``i``'s output reach the DAG's output
        (the sink, or any terminal when no sink is set)?"""
        outputs = ({self.sink} if self.sink is not None
                   else set(_terminals(self.edges)))
        live = set(outputs)
        mask = [False] * len(self.edges)
        # walk edges in reverse topological (list) order: an edge is live
        # when its dst is, and then so are its inputs
        for i in range(len(self.edges) - 1, -1, -1):
            if self.edges[i].dst in live:
                mask[i] = True
                live.update(self.edges[i].src)
        return mask

    def validate_structure(self) -> None:
        """The invariants every machine-generated structure must satisfy,
        beyond :meth:`validate`'s topological ordering: at least one edge,
        an output to reduce, and every edge connected to it (a mutation
        must never leave dead compute the metric vector charges for but
        the workload semantics cannot justify)."""
        try:
            self.validate()
        except ValueError as e:
            raise StructureError(str(e)) from e
        if not self.edges:
            raise StructureError(f"{self.name}: structure has no edges")
        dead = [i for i, ok in enumerate(self.contributing_mask()) if not ok]
        if dead:
            names = [f"{i}:{self.edges[i].component}" for i in dead[:4]]
            raise StructureError(
                f"{self.name}: edges {names} do not reach the "
                f"{'sink' if self.sink is not None else 'terminals'}")

    # -- build (thin shims over the ExecutionPlan lowering pipeline) ---------

    def _legacy_plan(self):
        """Fresh *unfused* plan (one stage per edge, current params baked):
        the exact legacy execution semantics every ``build*`` shim keeps."""
        from .schedule import lower
        return lower(self, threshold=0.0, cache=False)

    def build(self) -> Callable[[jax.Array], jnp.ndarray]:
        """Returns a jit-able fn(rng) -> scalar executing the whole DAG."""
        return self._legacy_plan().build()

    def build_parametric(self) -> Callable:
        """Returns ``fn(rng, dyn) -> scalar`` where ``dyn`` is a
        :meth:`dynamic_params`-shaped pytree of traced scalars — the
        compile-once/run-many execution form."""
        return self._legacy_plan().build_parametric()

    def build_population(self) -> Callable:
        """Returns ``fn(rng, dyn_batched) -> (n,)`` evaluating a whole
        *population* of dynamic-param candidates in one call:
        ``dyn_batched`` is a :meth:`dynamic_params`-shaped pytree whose
        leaves carry a leading candidate axis (see
        ``ParamSpace.stack_candidates``), vmapped over so every candidate
        shares the rng, the generated sources, and — once jitted — a
        single compiled executable (zero retraces per candidate).  Stacks
        additionally stratify candidate batches into weight buckets (see
        :meth:`repro.core.schedule.ExecutionPlan.bucket_schedule`)."""
        return self._legacy_plan().build_population()

    def build_stages(self):
        """Deprecated per-edge staging (see :meth:`build_stages_parametric`
        for the protocol); staged drivers consume
        ``schedule.lower(dag).stages_parametric()`` — fused-stage
        granularity — instead."""
        init_fn, stages, finalize_fn = self.build_stages_parametric()
        return (init_fn,
                [(srcs, dst, (lambda s: lambda rng, xs, prev:
                              s(rng, xs, prev, None))(stage))
                 for srcs, dst, stage, _key in stages],
                finalize_fn)

    def build_stages_parametric(self):
        """Deprecated: use ``schedule.lower(dag).stages_parametric()``.

        Legacy protocol kept for old staged drivers: stages are
        ``(src_names, dst, stage_fn, stage_key)`` with
        ``stage_fn(rng, xs, prev, dyn_e)`` taking the *edge's* dynamic
        param dict (or ``None``) and ``stage_key`` the
        ``(edge_idx, Edge.structure_key())`` pair.  The ExecutionPlan form
        differs in granularity (fused stages) and passes the member dyn
        dicts as a tuple."""
        warnings.warn(
            "ProxyDAG.build_stages_parametric is deprecated; use "
            "repro.core.schedule.lower(dag).stages_parametric()",
            DeprecationWarning, stacklevel=2)
        init_fn, stages, finalize_fn = \
            self._legacy_plan().stages_parametric()
        legacy = []
        for srcs, dst, fn, key in stages:
            members, skeys = key
            legacy.append(
                (srcs, dst,
                 (lambda f: lambda rng, xs, prev, dyn_e:
                  f(rng, xs, prev, (dyn_e,)))(fn),
                 (members[0], skeys[0])))
        return init_fn, legacy, finalize_fn

    # -- serialization -------------------------------------------------------

    def to_json(self) -> Dict:
        return {
            "name": self.name,
            "sources": dict(self.sources),
            "edges": [e.to_json() for e in self.edges],
            "sink": self.sink,
        }

    @classmethod
    def from_json(cls, d: Dict) -> "ProxyDAG":
        return cls(name=d["name"],
                   sources={k: int(v) for k, v in d["sources"].items()},
                   edges=[Edge.from_json(e) for e in d["edges"]],
                   sink=d.get("sink"))

    # -- deprecated tuner plumbing ------------------------------------------
    # The auto-tuner now operates on repro.api.params.ParamSpace (a named
    # pytree with per-leaf bounds); these string handles remain as thin
    # shims for old callers.

    def get_param(self, edge_idx: int, field: str) -> float:
        warnings.warn("ProxyDAG.get_param is deprecated; use "
                      "repro.api.ParamSpace", DeprecationWarning, stacklevel=2)
        p = self.edges[edge_idx].params
        return float(p.extra[field] if field in p.extra else getattr(p, field))

    def set_param(self, edge_idx: int, field: str, value: float) -> None:
        warnings.warn("ProxyDAG.set_param is deprecated; use "
                      "repro.api.ParamSpace", DeprecationWarning, stacklevel=2)
        e = self.edges[edge_idx]
        if field in e.params.extra:
            e.params.extra[field] = value
        else:
            setattr(e.params, field, value)

    def param_space(self) -> List[tuple]:
        """Deprecated: legacy ``(edge_idx, field)`` handles.  Use
        :class:`repro.api.ParamSpace` for the named, bounded pytree view."""
        warnings.warn("ProxyDAG.param_space is deprecated; use "
                      "repro.api.ParamSpace", DeprecationWarning, stacklevel=2)
        from ..api.params import ParamSpace
        space = ParamSpace.from_dag(self)
        return [space.handle(i) for i in range(len(space))]


# ---------------------------------------------------------------------------
# structure mutation primitives (the Fig.-3 design-space moves)
# ---------------------------------------------------------------------------
#
# Each primitive is pure: it returns a NEW ProxyDAG (the input is never
# touched) that satisfies ``validate_structure`` whenever the input did,
# or raises StructureError when the requested move is illegal at that
# site.  The structural search (repro.core.structsearch) composes these
# into mutation proposals; the primitives themselves are deterministic so
# a mutation sequence replays identically from a seed.


def _copy_edges(edges: Sequence[Edge]) -> List[Edge]:
    return [Edge(e.component, list(e.src), e.dst,
                 dataclasses.replace(e.params, extra=dict(e.params.extra)))
            for e in edges]


def fresh_node(dag: ProxyDAG, prefix: str = "m") -> str:
    """First ``{prefix}{k}`` name unused by any node of ``dag`` —
    deterministic, so mutated structures serialize reproducibly."""
    used = set(dag.sources) | {e.dst for e in dag.edges}
    used.update(s for e in dag.edges for s in e.src)
    k = 0
    while f"{prefix}{k}" in used:
        k += 1
    return f"{prefix}{k}"


def _neighbor_params(e: Edge, component: str, weight: int) -> ComponentParams:
    """Params for a machine-inserted edge: the neighbouring edge's shape
    fields (the chain's carry size), no inherited extras — extras encode
    component-specific semantics the new component may not share."""
    p = e.params.rounded()
    return ComponentParams(data_size=p.data_size, chunk_size=p.chunk_size,
                           parallelism=p.parallelism, weight=int(weight))


def insert_edge(dag: ProxyDAG, idx: int, component: str,
                weight: int = 1) -> ProxyDAG:
    """Splice a new ``component`` edge into edge ``idx``'s input chain:
    the new edge reads edge ``idx``'s (single) input and edge ``idx`` is
    rewired to read the new intermediate node instead."""
    get_component(component)                 # unknown names fail fast
    e = dag.edges[idx]
    if len(e.src) != 1:
        raise StructureError(
            f"insert_edge: edge {idx} ({e.component}) has {len(e.src)} "
            f"inputs; splicing needs a single-input edge")
    mid = fresh_node(dag)
    edges = _copy_edges(dag.edges)
    edges[idx] = Edge(e.component, [mid], e.dst,
                      dataclasses.replace(e.params,
                                          extra=dict(e.params.extra)))
    new = Edge(component, list(e.src), mid,
               _neighbor_params(e, component, weight))
    edges.insert(idx, new)
    out = ProxyDAG(dag.name, dict(dag.sources), edges, dag.sink)
    out.validate_structure()
    return out


def insert_accumulating_edge(dag: ProxyDAG, src: str, dst_idx: int,
                             component: str, weight: int = 1) -> ProxyDAG:
    """Add a new ``component`` edge accumulating into edge ``dst_idx``'s
    output node (shared-dst addition, the DAG's join semantics), placed
    right after that producer so every downstream consumer sees the
    contribution.  ``src`` must be a node defined before the insertion
    point."""
    get_component(component)
    e = dag.edges[dst_idx]
    defined = set(dag.sources)
    for prior in dag.edges[: dst_idx + 1]:
        defined.add(prior.dst)
    if src not in defined:
        raise StructureError(
            f"insert_accumulating_edge: node {src!r} is not defined at "
            f"edge {dst_idx}")
    edges = _copy_edges(dag.edges)
    edges.insert(dst_idx + 1,
                 Edge(component, [src], e.dst,
                      _neighbor_params(e, component, weight)))
    out = ProxyDAG(dag.name, dict(dag.sources), edges, dag.sink)
    out.validate_structure()
    return out


def remove_edge(dag: ProxyDAG, idx: int) -> ProxyDAG:
    """Delete edge ``idx``.  An accumulating edge (its dst has another
    producer) simply drops; otherwise its consumers are bypassed onto the
    edge's first input (and the sink re-points likewise), so the DAG
    stays connected."""
    if len(dag.edges) <= 1:
        raise StructureError("remove_edge: structure has only one edge")
    e = dag.edges[idx]
    others = [o for j, o in enumerate(dag.edges) if j != idx]
    edges = _copy_edges(others)
    sink = dag.sink
    if not any(o.dst == e.dst for o in others):
        # sole producer: bypass consumers (and the sink) onto its input
        repl = e.src[0]
        for o in edges:
            o.src = [repl if s == e.dst else s for s in o.src]
        if sink == e.dst:
            sink = repl
        if sink is not None and sink not in set(dag.sources) | {
                o.dst for o in edges}:
            raise StructureError(
                f"remove_edge: removing edge {idx} orphans sink {sink!r}")
    out = ProxyDAG(dag.name, dict(dag.sources), edges, sink)
    out.validate_structure()
    return out


def swap_component(dag: ProxyDAG, idx: int, component: str) -> ProxyDAG:
    """Replace edge ``idx``'s dwarf component, keeping topology and shape
    params.  Extras are dropped: they parameterize the *old* component's
    semantics (hash rounds, histogram bins) and stale keys would leak
    into the new edge's static structure key."""
    get_component(component)
    e = dag.edges[idx]
    if component == e.component:
        raise StructureError(f"swap_component: edge {idx} already is "
                             f"{component!r}")
    edges = _copy_edges(dag.edges)
    edges[idx] = Edge(component, list(e.src), e.dst,
                      _neighbor_params(e, component,
                                       e.params.rounded().weight))
    out = ProxyDAG(dag.name, dict(dag.sources), edges, dag.sink)
    out.validate_structure()
    return out


def split_edge(dag: ProxyDAG, idx: int, first_weight: int) -> ProxyDAG:
    """Split edge ``idx`` (weight ``w >= 2``) into a chain of two
    same-component edges with weights ``first_weight`` and
    ``w - first_weight`` through a fresh intermediate node — the inverse
    of :func:`merge_chain`, and the move that exposes a chain position
    for a later :func:`swap_component`."""
    e = dag.edges[idx]
    w = e.params.rounded().weight
    first_weight = int(first_weight)
    if w < 2 or not 0 < first_weight < w:
        raise StructureError(
            f"split_edge: edge {idx} weight {w} cannot split at "
            f"{first_weight}")
    mid = fresh_node(dag)
    edges = _copy_edges(dag.edges)
    edges[idx] = Edge(e.component, [mid], e.dst,
                      dataclasses.replace(
                          e.params, weight=w - first_weight,
                          extra=dict(e.params.extra)))
    edges.insert(idx, Edge(e.component, list(e.src), mid,
                           dataclasses.replace(
                               e.params, weight=first_weight,
                               extra=dict(e.params.extra))))
    out = ProxyDAG(dag.name, dict(dag.sources), edges, dag.sink)
    out.validate_structure()
    return out


def merge_chain(dag: ProxyDAG, idx: int) -> ProxyDAG:
    """Merge edges ``idx`` and ``idx + 1`` — a private same-component
    chain — into one edge with the summed weight."""
    if idx + 1 >= len(dag.edges):
        raise StructureError(f"merge_chain: no edge after {idx}")
    a, b = dag.edges[idx], dag.edges[idx + 1]
    consumers = [j for j, o in enumerate(dag.edges)
                 for s in o.src if s == a.dst]
    mergeable = (a.component == b.component
                 and list(b.src) == [a.dst]
                 and consumers == [idx + 1]
                 and sum(1 for o in dag.edges if o.dst == a.dst) == 1
                 and a.dst not in dag.sources and a.dst != dag.sink
                 and a.structure_key() == b.structure_key())
    if not mergeable:
        raise StructureError(
            f"merge_chain: edges {idx},{idx + 1} are not a private "
            f"same-structure chain")
    edges = _copy_edges(dag.edges)
    merged = Edge(a.component, list(a.src), b.dst,
                  dataclasses.replace(
                      b.params,
                      weight=a.params.rounded().weight
                      + b.params.rounded().weight,
                      extra=dict(b.params.extra)))
    edges[idx: idx + 2] = [merged]
    out = ProxyDAG(dag.name, dict(dag.sources), edges, dag.sink)
    out.validate_structure()
    return out
