"""BLEST's static execution policy (paper §5, Table 2 "full" variant).

The paper's pipeline makes two static decisions per graph:
  1. ordering: social-like -> JaccardWithWindows (+pre-pass); else RCM;
  2. update scheme: lazy vertex updates only when the update divergence
     exceeds a threshold (paper: 25,000) — the lazy Θ(n) sweep pays off on
     low-diameter social graphs with scattered updates, and hurts on
     high-diameter graphs (Spielman_k600's 600 levels in the paper).

``prepare(graph)`` runs the whole static pipeline and returns a ready
engine; this is exactly what BLEST (full) does before the first BFS.  It is
the ONE ordering/BVSS/engine preparation in the tree: the launcher, the
serving layer (``repro.serve.GraphSession``) and the examples all go
through it rather than re-implementing order -> permute -> BVSS -> engine.

``prepare(graph, mesh=...)`` is the one SHARDED preparation too (DESIGN
§2.4): the same classify/order/scheme decisions run on the global BVSS,
then the problem is built row-sharded over the mesh axis and the engines
run the same fused pipeline under ``shard_map``.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import tracing
from repro.core.bfs import BlestProblem, level_counter_names, make_engine
from repro.core.bvss import (BVSS, build_bvss, build_sharded_bvss,
                             build_sharded_weight_plane, build_weight_plane,
                             weight_plane_to_device)
from repro.core.ordering import auto_order
from repro.errors import BlestError, ConfigError, check_source, check_weights
from repro.graphs import Graph

# paper §5: fixed threshold for switching to lazy vertex updates
LAZY_UDIV_THRESHOLD = 25_000.0
# at lab scale the same mechanism is exercised with a proportional
# threshold (the paper's constant assumes 23M+ vertex graphs)
LAZY_UDIV_FRACTION = 0.1


@dataclasses.dataclass(frozen=True, eq=False)
class PrepareOptions:
    """Every static knob of :func:`prepare`, as one typed value.

    The public way to configure a preparation::

        prepare(g, options=PrepareOptions(sigma=4, direction="pull"))

    The options ride along on ``PreparedBFS.options`` so downstream
    maintenance (:func:`repro.core.bvss_delta.apply_edge_updates`) can
    rebuild engines — or fall back to a full re-``prepare`` — with
    exactly the knobs the original preparation used.  ``eq=False``:
    ``weights`` may be an array, and identity is the only comparison a
    frozen bag of build knobs needs.
    """

    sigma: int = 8                        # slice width (bits)
    w: int = 512                          # ordering window
    seed: int = 0                         # ordering shingle seed
    lazy_threshold: float | None = None   # lazy-update divergence override
    order: bool = True                    # run the ordering pre-pass
    engine: str | None = None             # explicit engine override
    use_kernels: bool = True              # Pallas kernels vs pure-jnp twins
    direction: str = "auto"               # push/pull hybrid mode
    push_impl: Callable | None = None     # push-kernel fault seam
    mesh: Mesh | None = None              # row-shard over this device mesh
    mesh_axis: str = "data"               # row axis name of the mesh
    weights: np.ndarray | None = None     # per-edge weights of the INPUT g

    def replace(self, **changes) -> "PrepareOptions":
        return dataclasses.replace(self, **changes)


@dataclasses.dataclass
class PreparedBFS:
    graph: Graph           # reordered graph
    perm: np.ndarray       # old id -> new id
    inv: np.ndarray        # new id -> old id (perm's inverse)
    ordering: str
    engine_name: str
    bvss: BVSS
    # device-resident BVSS bundle, shared with the engine; None when the
    # prepared engine is a CSR/dense baseline that never touches the BVSS
    problem: BlestProblem | None
    update_divergence: float
    # mesh the problem is row-sharded over; None = single-device
    mesh: Mesh | None = None
    # per-edge weights in the REORDERED graph's CSR edge order (float32)
    # and their device-committed weight plane (DESIGN §2.9: the min-plus /
    # weighted-verb operand, +inf dummy row appended); None = unweighted
    weights: np.ndarray | None = None
    wplane: "object | None" = None
    # the exact knobs this preparation ran with — incremental maintenance
    # (core/bvss_delta.py) rebuilds engines / re-prepares through them
    options: "PrepareOptions | None" = None
    # epoch version of the prepared state (DESIGN §2.10): bumped by every
    # apply_edge_updates; in-flight waves keep pulling the buffers of the
    # epoch they were built against (JAX arrays are immutable)
    epoch: int = 0
    # cumulative edges patched since the last FULL build — the staleness
    # ledger apply_edge_updates charges its budget against
    stale_edges: int = 0
    # the UpdateReport of the apply_edge_updates call that produced this
    # epoch (None on a fresh preparation)
    last_update: "object | None" = None
    _fn: Callable | None = dataclasses.field(default=None)
    # ``_fn`` compiled on the first query; the calls run this executable,
    # whose device operations the tracer maps to their stages
    _compiled: Callable | None = dataclasses.field(default=None, init=False,
                                                   repr=False)

    def _engine(self) -> Callable:
        if self._compiled is None:
            self._compiled = self._fn.lower(
                jax.ShapeDtypeStruct((), jnp.int32)).compile()
            tracing.map_stages(self._compiled.as_text())
        return self._compiled

    def levels(self, src: int) -> np.ndarray:
        """BFS levels in the caller's (original) vertex ids.  A counted
        engine's level counters go to the tracer, as ``bfs.<name>``,
        against this query."""
        if self._fn is None:
            raise BlestError("PreparedBFS built without an engine")
        with tracing.query():
            src = check_source(src, self.graph.n)
            with tracing.span("repro.query.dispatch"):
                out = self._engine()(np.int32(self.perm[src]))
            with tracing.span("repro.query.wait"):
                out = jax.block_until_ready(out)
            with tracing.span("repro.query.copy"):
                out = jax.device_get(out)
            if isinstance(out, tuple):
                out, counts = out
                tracing.record(dict(zip(
                    ("bfs." + k for k in level_counter_names(len(counts))),
                    counts.tolist())))
            with tracing.span("repro.query.unpermute"):
                return out[self.perm]


def choose_update_scheme(bvss: BVSS, *, threshold: float | None = None
                         ) -> str:
    """Paper §5: lazy updates iff the update divergence is high (scattered
    updates dominate) — otherwise the eager scheme avoids the Θ(n) sweep."""
    udiv = bvss.update_divergence()
    if threshold is None:
        threshold = min(LAZY_UDIV_THRESHOLD, LAZY_UDIV_FRACTION * bvss.n)
    return "blest_lazy" if udiv > threshold else "blest"


BVSS_ENGINES = ("brs", "blest", "blest_lazy")


def build_problem(g_ord: Graph, *, sigma: int = 8, mesh: Mesh | None = None,
                  mesh_axis: str = "data", bvss=None,
                  options: "PrepareOptions | None" = None) -> BlestProblem:
    """Build the device problem for an (already ordered) graph: single-
    device, 1-D row-sharded, or 2-D row × column-sharded depending on the
    mesh — the ONE dispatch every problem-building caller (``prepare``,
    the serving tier's symmetrised problem) routes through.  A 2-D mesh
    (two named axes) partitions by ``(rows, cols) = mesh.devices.shape``;
    ``mesh_axis`` then names the ROW axis and must be the mesh's first.
    ``options`` supplies ``sigma``/``mesh``/``mesh_axis`` in one value (an
    explicitly passed kwarg wins — callers like the serving tier's
    symmetrised problem override the mesh per build)."""
    if options is not None:
        sigma = options.sigma if sigma == 8 else sigma
        mesh = options.mesh if mesh is None else mesh
        mesh_axis = options.mesh_axis if mesh_axis == "data" else mesh_axis
    if mesh is None:
        if bvss is None:
            bvss = build_bvss(g_ord, sigma=sigma)
        return BlestProblem.build(bvss)
    from repro.distributed.bfs_dist import mesh_is_2d
    if mesh_is_2d(mesh):
        sb = build_sharded_bvss(g_ord, tuple(mesh.devices.shape),
                                sigma=sigma)
        return BlestProblem.build_sharded_2d(sb, mesh)
    sb = build_sharded_bvss(g_ord, mesh.shape[mesh_axis], sigma=sigma)
    return BlestProblem.build_sharded(sb, mesh, mesh_axis)


#: legal legacy keywords of :func:`prepare` = the PrepareOptions fields
_PREPARE_FIELDS = tuple(f.name for f in dataclasses.fields(PrepareOptions))


def prepare(g: Graph, options: PrepareOptions | None = None,
            **legacy) -> PreparedBFS:
    """The full static pipeline: (optionally) order, build the BVSS, pick
    the update scheme (or honour an explicit ``engine`` override, e.g. the
    Table-2 ablation variants), build the fused engine.

    Configuration comes in as one :class:`PrepareOptions` value::

        prepare(g, options=PrepareOptions(sigma=4, mesh=mesh))

    The pre-0.5 keyword spelling ``prepare(g, sigma=4, mesh=mesh)`` still
    works as a thin shim that builds the options for you and emits a
    ``DeprecationWarning`` — passing both forms at once is a
    :class:`~repro.errors.ConfigError`.

    Knob semantics (see :class:`PrepareOptions` for the full list):

    * ``direction`` selects the push/pull hybrid mode of the BVSS engines
      (DESIGN §2.8; default "auto" picks per level on device);
      ``push_impl`` overrides the push kernel — the single-source push
      fault seam (DESIGN §2.7), threaded through by the serving tier's
      :class:`~repro.serve.faults.FaultPlan`.
    * ``mesh`` row-shards the problem over ``mesh_axis`` and builds the
      mesh-native engine (DESIGN §2.4): the policy decisions (ordering,
      update scheme) still come from the global BVSS, the level loop runs
      under ``shard_map``.  This is the ONE sharded-prep entry point.
    * ``weights`` (one float per CSR edge of ``g``, validated strictly
      positive) threads an edge-weight plane through the whole pipeline
      (DESIGN §2.9): the weights ride the ordering permutation alongside
      the edges and land device-side in the BVSS slice layout
      (``PreparedBFS.wplane``), ready for the min-plus / weighted verbs.

    The returned :class:`PreparedBFS` starts at ``epoch 0``; streaming
    edge updates evolve it through
    :func:`repro.core.bvss_delta.apply_edge_updates` (DESIGN §2.10)."""
    if legacy:
        unknown = sorted(set(legacy) - set(_PREPARE_FIELDS))
        if unknown:
            raise TypeError(
                f"prepare() got unexpected keyword arguments {unknown} "
                f"(valid PrepareOptions fields: {list(_PREPARE_FIELDS)})")
        if options is not None:
            raise ConfigError(
                "prepare() takes EITHER options=PrepareOptions(...) or the "
                "deprecated per-knob keywords, not both — fold "
                f"{sorted(legacy)} into the options value")
        warnings.warn(
            "prepare(g, sigma=..., w=..., ...) keywords are deprecated; "
            "pass prepare(g, options=PrepareOptions(...)) instead",
            DeprecationWarning, stacklevel=2)
        options = PrepareOptions(**legacy)
    elif options is None:
        options = PrepareOptions()
    o = options
    with tracing.span("repro.prepare"):
        with tracing.span("repro.prepare.ordering"):
            perm, kind, g_ord, inv, w_ord = _order(g, o)
        with tracing.span("repro.prepare.bvss"):
            bvss, engine_name, problem, sb = _build_bvss(g_ord, w_ord, o)
        wplane = None
        if w_ord is not None:
            with tracing.span("repro.prepare.weights"):
                wplane = _build_weight_plane(g_ord, w_ord, sb, o)
        with tracing.span("repro.prepare.engine"):
            fn = build_engine(g_ord, engine_name, bvss, problem, o)
    return PreparedBFS(graph=g_ord, perm=perm, inv=inv, ordering=kind,
                       engine_name=engine_name, bvss=bvss, problem=problem,
                       update_divergence=bvss.update_divergence(),
                       mesh=o.mesh, weights=w_ord, wplane=wplane,
                       options=o, _fn=fn)


def _order(g: Graph, o: PrepareOptions):
    """The ordering stage: ``(perm, kind, g_ord, inv, w_ord)``."""
    w_arr = None if o.weights is None else check_weights(o.weights, g.m)
    if o.order:
        perm, kind = auto_order(g, sigma=o.sigma, w=o.w, seed=o.seed)
        g_ord = g.permute_fast(perm)
    else:
        perm, kind = np.arange(g.n, dtype=np.int64), "natural"
        g_ord = g
    inv = np.empty(g.n, dtype=np.int64)
    inv[perm] = np.arange(g.n)
    w_ord = None
    if w_arr is not None:
        if o.order:
            # permute_fast re-sorts the relabelled edges by (src·n + dst)
            # key; simple-graph keys are unique, so a stable argsort maps
            # each ordered edge back to its original weight
            from repro.graphs import src_of_edges
            keys = (perm[src_of_edges(g)] * np.int64(g.n)
                    + perm[g.indices.astype(np.int64)])
            w_ord = w_arr[np.argsort(keys, kind="stable")]
        else:
            w_ord = w_arr
    return perm, kind, g_ord, inv, w_ord


def _build_bvss(g_ord: Graph, w_ord, o: PrepareOptions):
    """The BVSS stage: the host BVSS, the update scheme and the device
    problem, ``(bvss, engine_name, problem, sb)``; ``sb`` is the sharded
    BVSS a weighted 1-D mesh problem was built from (else None), which
    its weight plane must align with."""
    bvss = build_bvss(g_ord, sigma=o.sigma)
    engine_name = o.engine if o.engine is not None else \
        choose_update_scheme(bvss, threshold=o.lazy_threshold)
    sb = None
    if o.mesh is not None:
        if engine_name not in BVSS_ENGINES:
            raise ValueError(
                f"mesh-native prepare supports the BVSS engines "
                f"{BVSS_ENGINES}, not {engine_name!r} (the CSR/dense "
                f"baselines have no row-sharded representation)")
        from repro.distributed.bfs_dist import mesh_is_2d
        if w_ord is not None and mesh_is_2d(o.mesh):
            raise ConfigError(
                "edge weights are not supported on a 2-D (row × column) "
                "mesh yet — the weighted verbs ship 1-D row-sharded "
                "(DESIGN §2.9); use a 1-D mesh or a single device")
        if w_ord is not None:
            # build the sharded BVSS once and derive both the problem and
            # the aligned per-shard weight planes from it
            sb = build_sharded_bvss(g_ord, o.mesh.shape[o.mesh_axis],
                                    sigma=o.sigma)
            problem = BlestProblem.build_sharded(sb, o.mesh, o.mesh_axis)
        else:
            problem = build_problem(g_ord, sigma=o.sigma, mesh=o.mesh,
                                    mesh_axis=o.mesh_axis)
    else:
        # only BVSS-consuming single-source engines need the device upload;
        # the host bvss alone backs the stats printouts and the policy
        problem = BlestProblem.build(bvss) if engine_name in BVSS_ENGINES \
            else None
    return bvss, engine_name, problem, sb


def _build_weight_plane(g_ord: Graph, w_ord, sb, o: PrepareOptions):
    """The weight-plane stage: the plane in the BVSS slice layout (per
    shard of ``sb`` on a 1-D mesh), committed to the device."""
    if sb is not None:
        return weight_plane_to_device(
            build_sharded_weight_plane(g_ord, w_ord, sb), o.mesh,
            o.mesh_axis)
    return weight_plane_to_device(
        build_weight_plane(g_ord, w_ord, sigma=o.sigma))


def build_engine(g_ord: Graph, engine_name: str, bvss: BVSS, problem,
                 o: PrepareOptions) -> Callable:
    """The prepared single-source engine; the single-device blest and
    blest_lazy engines also return their level counters."""
    counted = engine_name in ("blest", "blest_lazy") and o.mesh is None
    return make_engine(g_ord, engine_name, bvss=bvss, problem=problem,
                       use_kernels=o.use_kernels, direction=o.direction,
                       push_impl=o.push_impl, counters=counted)


def parents_from_levels(g: Graph, levels: np.ndarray) -> np.ndarray:
    """BFS parent array (paper §2: the kernel may return either form).

    Pull semantics: parent[u] is any in-neighbour of u at level[u]-1 (the
    first in in-CSR order).  One vectorised NumPy sweep over the in-CSR."""
    INF = np.iinfo(np.int32).max
    t_indptr, t_indices = g.t_csr
    levels = np.asarray(levels)
    u_of = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(t_indptr))
    lu = levels[u_of]
    nbrs = t_indices.astype(np.int64)
    ok = (lu != 0) & (lu != INF) & (levels[nbrs] == lu - 1)
    parents = np.full(g.n, -1, dtype=np.int64)
    idx = np.flatnonzero(ok)
    if len(idx):
        # first qualifying in-edge per vertex: idx ascends within each
        # CSR row, so unique's first occurrence is the CSR-order choice
        uu, first = np.unique(u_of[idx], return_index=True)
        parents[uu] = nbrs[idx[first]]
    return parents
