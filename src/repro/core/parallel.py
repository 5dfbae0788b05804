"""The sharded ``parallel`` engine: mesh-partitioned whole-query
execution as a first-class stages back-end (paper section 4.3).

Flare parallelises operators *internally*: a parallel scan fans work out
to threads, join/aggregate implement thread-safe consume, and per-thread
partial aggregates merge after the parallel section.  The mesh version
here is structurally identical (and Sparkle's NUMA-partitioned Spark
makes the same argument at rack scale):

* the probe-side (spine) table is row-partitioned across a named mesh
  axis (NUMA data partitioning -> ``PartitionSpec(axis)``),
* build-side tables are replicated (the paper's broadcast hash build),
* each shard runs the SAME whole-query program on its row range -- the
  trace comes from ``lower.build_callable``, so ``param()`` placeholders
  ride through as traced scalars and native kernel dispatch
  (``repro.native``) composes per shard,
* the merge after the parallel section is explicit in the plan: a
  :class:`ShardMerge` node psum/pmin/pmax-merges the dense per-shard
  group vectors ("per-thread data structures merged after the parallel
  section"), with ``avg`` recomposed from merged sum/count, and a
  :class:`ShardGather` node all-gathers row streams for operators that
  need the whole relation (sort/limit and other non-distributive
  finishes -- "gather-and-finish on the host shard").

Shard planning (:func:`shard_plan`) splits the optimized plan at the
deepest spine operator that cannot run shard-locally:

====================  =====================================================
spine shape            strategy
====================  =====================================================
... -> Aggregate       merge: shard-local partial aggregate (avg rewritten
                       to sum [+ count]), dense group vectors merged with
                       psum/pmin/pmax, avg recomposed, finish ops
                       (sort/limit/project) run replicated post-merge
... -> Sort/Limit      gather: the shard-local prefix (Filter/Project/
                       Join/MapBatches chains) runs partitioned, then the
                       stream is all-gathered and the rest runs replicated
plain chains           gather at the root
====================  =====================================================

The rewrite happens at ``lower()`` time, so the mesh axis and shard
count are part of the plan fingerprint: one compiled template per mesh
shape, shared across ``param()`` bindings (DESIGN.md section 9).

Surface::

    lowered  = df.lower(engine="parallel", mesh=mesh, axis="data")
    compiled = lowered.compile()     # ONE SPMD XLA program, AOT
    compiled(**bindings)             # prepared execution, zero recompiles

``mesh=None`` builds a 1-D data mesh over every host device
(``repro.launch.mesh.make_data_mesh``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import engines as ENG
from repro.core import expr as E
from repro.core import lower as L
from repro.core import plan as PL
from repro.core import stages as S
from repro.native import registry as R
from repro.relational import table as T
from repro.resilience import faults as FZ


class UnsupportedParallelPlan(TypeError):
    """Plan shape the parallel engine cannot shard (asserted explicitly
    in the engine differential matrix rather than silently skipped)."""


#: Spine operators that are row-parallel: they act per probe-side row
#: (Join probes against a replicated build side), so a row-partitioned
#: shard computes exactly its slice of the full operator output.
_SPINE_SAFE = (PL.Filter, PL.Project, PL.Join, PL.MapBatches)

#: Merge collective per aggregate op.  ``avg`` is non-distributive and
#: never merged directly: shard planning rewrites it to a sum partial
#: and recomposes from merged sum/count (see :func:`_partial_of`).
_MERGE_OPS = {"sum": "psum", "count": "psum", "min": "pmin",
              "max": "pmax", "any": "pmax"}

_SYNTH_COUNT = "__pcount"


def _mesh_device_ids(mesh: Optional[Mesh]) -> Tuple[int, ...]:
    """Device identity of a mesh, for template fingerprints: a compiled
    executable is pinned to its devices, so same-shape meshes over
    different device subsets must get distinct cache entries."""
    if mesh is None:
        return ()
    return tuple(d.id for d in mesh.devices.flat)


# ---------------------------------------------------------------------------
# shard-plan IR: the merge / gather nodes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class ShardMerge(PL.Plan):
    """Merge point of the parallel section: ``child`` is the shard-local
    partial aggregate (possibly NativeOp-annotated); lowering merges its
    dense group vectors across the mesh axis and recomposes ``avg``
    columns from merged sum/count.  Implements the custom-lowering
    protocol of ``repro.core.lower``, so ``build_callable`` traces the
    collectives into the same SPMD program as the surrounding operators.
    """

    child: PL.Plan
    original: PL.Aggregate            # pre-rewrite aggregate (schema truth)
    merges: Tuple[Tuple[str, str], ...]  # (partial column, agg op)
    avg_names: Tuple[str, ...]        # columns to recompose as sum/count
    count_name: Optional[str]         # merged count used for avg + mask
    synthetic: Optional[str]          # added count column to drop
    axis: str
    n_shards: int
    pad_to: int                       # padded spine length (all shards)
    true_rows: int                    # real spine rows (mask bound)
    mesh: Any = dataclasses.field(default=None, repr=False)
    spine: Any = dataclasses.field(default=None, repr=False)  # Scan node

    def children(self) -> Tuple[PL.Plan, ...]:
        return (self.child,)

    def with_children(self, kids):
        return dataclasses.replace(self, child=kids[0])

    def infer_schema(self, catalog):
        return self.original.schema(catalog)

    def describe(self):
        return (f"ShardMerge[{self.axis}x{self.n_shards}] "
                + ", ".join(f"{n}:{op}" for n, op in self.merges))

    def fingerprint(self):
        # axis + shard count + device identity ARE the template
        # identity: one compiled program per mesh (same-shape meshes
        # over DIFFERENT devices must not share an executable), plus
        # the pre-rewrite aggregate, since two originals -- avg vs sum
        # -- share one partial form
        return (f"shardmerge[{self.axis}:{self.n_shards}:"
                f"{_mesh_device_ids(self.mesh)}]"
                f"({self.child.fingerprint()};"
                f"{self.original.fingerprint()})")

    # -- repro.core.lower custom-lowering protocol ---------------------------

    def static_info_hook(self, catalog) -> L.StaticInfo:
        return L.static_info(self.original, catalog)

    def required_columns_hook(self, rec, needed) -> None:
        rec(self.child, needed)

    def lower_stream(self, catalog, scans, params) -> L.Stream:
        s = L.lower_node(self.child, catalog, scans, params)
        merged: Dict[str, jnp.ndarray] = {}
        for name, op in self.merges:
            v = s.cols[name]
            coll = _MERGE_OPS[op]
            if coll == "psum":
                merged[name] = jax.lax.psum(v, self.axis)
            elif coll == "pmin":
                merged[name] = jax.lax.pmin(v, self.axis)
            else:
                merged[name] = jax.lax.pmax(v, self.axis)
        cnt = merged.get(self.count_name)
        for name in self.avg_names:
            merged[name] = merged[name] / jnp.maximum(cnt, 1).astype(
                merged[name].dtype)
        # group keys are decoded from the group index -- identical on
        # every shard, no collective needed
        cols = {k: s.cols[k] for k in self.original.keys}
        for name, _ in self.merges:
            if name != self.synthetic:
                cols[name] = merged[name]
        mask = (cnt > 0) if self.original.keys else None
        return L.Stream(cols, mask, L.static_info(self.original, catalog))


@dataclasses.dataclass(eq=False)
class ShardGather(PL.Plan):
    """Gather point: ``child`` runs shard-locally (row-partitioned
    spine), then its columns and validity mask are all-gathered along the
    mesh axis so downstream operators (sort/limit, non-distributive
    finishes) see the whole padded relation, replicated -- the paper's
    "gather and finish on the master" for non-mergeable sections."""

    child: PL.Plan
    axis: str
    n_shards: int
    pad_to: int
    true_rows: int
    mesh: Any = dataclasses.field(default=None, repr=False)
    spine: Any = dataclasses.field(default=None, repr=False)

    def children(self) -> Tuple[PL.Plan, ...]:
        return (self.child,)

    def with_children(self, kids):
        return dataclasses.replace(self, child=kids[0])

    def infer_schema(self, catalog):
        return self.child.schema(catalog)

    def describe(self):
        return f"ShardGather[{self.axis}x{self.n_shards}]"

    def fingerprint(self):
        return (f"shardgather[{self.axis}:{self.n_shards}:"
                f"{_mesh_device_ids(self.mesh)}]"
                f"({self.child.fingerprint()})")

    # -- repro.core.lower custom-lowering protocol ---------------------------

    def static_info_hook(self, catalog) -> L.StaticInfo:
        child = L.static_info(self.child, catalog)
        return L.StaticInfo(child.cols, self.pad_to)

    def required_columns_hook(self, rec, needed) -> None:
        rec(self.child, needed)

    def lower_stream(self, catalog, scans, params) -> L.Stream:
        s = L.lower_node(self.child, catalog, scans, params)
        cols = {k: jax.lax.all_gather(v, self.axis, tiled=True)
                for k, v in s.cols.items()}
        mask = jax.lax.all_gather(s.the_mask(), self.axis, tiled=True)
        # shard-major concatenation == original row order (the spine is
        # padded then split into contiguous per-shard ranges)
        return L.Stream(cols, mask,
                        L.StaticInfo(s.info.cols, s.n * self.n_shards))


def find_shard_node(p: PL.Plan) -> Optional[PL.Plan]:
    """The (single) ShardMerge/ShardGather of a shard-planned plan."""
    if isinstance(p, (ShardMerge, ShardGather)):
        return p
    for c in p.children():
        found = find_shard_node(c)
        if found is not None:
            return found
    return None


# ---------------------------------------------------------------------------
# per-shard dispatch telemetry
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardedDispatchReport(R.DispatchReport):
    """Dispatch report of a native parallel template.  The program is
    SPMD -- every shard runs the same annotated plan -- so the decisions
    replicate; :attr:`per_shard` names them shard by shard."""

    n_shards: int = 1
    axis: str = "data"

    @property
    def per_shard(self) -> List[R.DispatchReport]:
        return [R.DispatchReport(decisions=list(self.decisions))
                for _ in range(self.n_shards)]

    def __str__(self) -> str:
        base = R.DispatchReport.__str__(self)
        return (f"{base}\n  (SPMD: x{self.n_shards} shards along "
                f"'{self.axis}')")


# ---------------------------------------------------------------------------
# shard planning
# ---------------------------------------------------------------------------


def _spine_path(p: PL.Plan) -> Tuple[List[PL.Plan], PL.Scan]:
    """Nodes from the root down to the spine (leftmost) scan."""
    path: List[PL.Plan] = []
    node = p
    while not isinstance(node, PL.Scan):
        path.append(node)
        if isinstance(node, PL.Join):
            node = node.left
        elif node.children():
            node = node.children()[0]
        else:
            raise UnsupportedParallelPlan(
                f"no spine scan below {node.describe()}")
    return path, node


def _rebuild(path: List[PL.Plan], idx: int, new_node: PL.Plan) -> PL.Plan:
    """Replace the spine node at ``path[idx]`` (or the spine scan when
    ``idx == len(path)``) and rebuild its ancestors."""
    cur = new_node
    for node in reversed(path[:idx]):
        kids = list(node.children())
        kids[0] = cur  # the spine is always the first child (child/left)
        cur = node.with_children(kids)
    return cur


def _partial_of(agg: PL.Aggregate) -> Tuple[PL.Aggregate, Tuple, Tuple,
                                            Optional[str], Optional[str]]:
    """The shard-local partial form of ``agg`` + its merge recipe.

    ``avg`` partials become sums (recomposed from merged sum/count after
    the collective); grouped aggregates always carry a count so the
    merged group mask (``count > 0``) is exact across shards.
    """
    count_name = next((a.name for a in agg.aggs if a.op == "count"), None)
    need_count = bool(agg.keys) or any(a.op == "avg" for a in agg.aggs)
    synthetic = None
    if need_count and count_name is None:
        synthetic = count_name = _SYNTH_COUNT
    partials: List[PL.AggSpec] = []
    merges: List[Tuple[str, str]] = []
    avg_names: List[str] = []
    for a in agg.aggs:
        if a.op == "avg":
            partials.append(PL.AggSpec(a.name, "sum", a.arg))
            merges.append((a.name, "sum"))
            avg_names.append(a.name)
        else:
            partials.append(a)
            merges.append((a.name, a.op))
    if synthetic is not None:
        partials.append(PL.AggSpec(synthetic, "count", None))
        merges.append((synthetic, "count"))
    partial = PL.Aggregate(agg.child, agg.keys, tuple(partials))
    return (partial, tuple(merges), tuple(avg_names), count_name, synthetic)


def shard_plan(p: PL.Plan, catalog: PL.Catalog, mesh: Optional[Mesh] = None,
               axis: str = "data", native: bool = False,
               join_index: bool = True,
               memory_budget: Optional[int] = None,
               morsel_rows: Optional[int] = None
               ) -> Tuple[PL.Plan, Optional[ShardedDispatchReport]]:
    """Rewrite an optimized plan for sharded execution on ``mesh``.

    Returns the shard-planned plan (containing exactly one
    :class:`ShardMerge` or :class:`ShardGather`) and, when
    ``native=True``, the per-shard dispatch report of the native
    kernel-annotation pass that ran over the sharded plan.

    ``memory_budget``/``morsel_rows`` compose out-of-core execution
    with sharding: each shard's partial aggregate is additionally
    wrapped in a :class:`repro.core.morsel.MorselMerge`, so every shard
    streams its OWN slice of the spine in bounded-memory morsels before
    the cross-shard collective merge.  The budget is per shard (each
    shard owns its accelerator's memory).
    """
    if mesh is None:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(axis=axis)
    if axis not in mesh.axis_names:
        raise ValueError(f"axis {axis!r} not in mesh axes "
                         f"{tuple(mesh.axis_names)}")
    n_shards = mesh.shape[axis]
    if isinstance(p, PL.IterativeKernel):
        raise UnsupportedParallelPlan(
            "IterativeKernel roots are not supported on the parallel "
            "engine: the training kernel consumes the whole gathered "
            "matrix on every shard; use engine='compiled' for "
            "heterogeneous pipelines")

    path, spine = _spine_path(p)
    true_rows = catalog.table(spine.table).num_rows
    pad_to = -(-true_rows // n_shards) * n_shards
    common = dict(axis=axis, n_shards=n_shards, pad_to=pad_to,
                  true_rows=true_rows, mesh=mesh, spine=spine)

    barrier_i = None
    for i, node in enumerate(path):
        if not isinstance(node, _SPINE_SAFE):
            barrier_i = i  # keep the last hit: the DEEPEST barrier

    out_of_core = memory_budget is not None or morsel_rows is not None
    merge_barrier = (barrier_i is not None
                     and isinstance(path[barrier_i], PL.Aggregate))
    if out_of_core and not merge_barrier:
        # gather-planned spine: no partials to merge, so a budget can
        # only pass through when the shard-local working set fits whole
        from repro.core import morsel as MO
        n_cols = len(L.required_scan_columns(p, catalog)
                     .get(id(spine), ())) or 1
        if (morsel_rows is not None
                or MO.working_set_bytes(n_cols, pad_to // n_shards)
                > memory_budget):
            raise MO.MemoryBudgetError(
                "memory budget needs a distributive aggregate on the "
                "spine to merge morsel partials behind; this sharded "
                "plan gathers instead of merging")
        out_of_core = False
    if merge_barrier:
        agg = path[barrier_i]
        partial, merges, avg_names, count_name, synthetic = _partial_of(agg)
        if out_of_core:
            # morselize the shard-local partial: _partial_of is
            # idempotent on it (no avg left, count already present), so
            # the inner MorselMerge hands ShardMerge exactly the partial
            # columns it expects, un-recomposed
            from repro.core import morsel as MO
            shard_rows = pad_to // n_shards
            n_cols = len(L.required_scan_columns(p, catalog)
                         .get(id(spine), ())) or 1
            partial = MO.morselize_aggregate(
                partial, spine, catalog, n_cols, shard_rows,
                memory_budget, morsel_rows)
        node = ShardMerge(child=partial, original=agg, merges=merges,
                          avg_names=avg_names, count_name=count_name,
                          synthetic=synthetic, **common)
        sharded = _rebuild(path, barrier_i, node)
    elif barrier_i is not None:
        ti = barrier_i + 1
        target = path[ti] if ti < len(path) else spine
        sharded = _rebuild(path, ti, ShardGather(child=target, **common))
    else:
        sharded = ShardGather(child=p, **common)

    report = None
    if native:
        from repro.native import dispatch as ND
        # annotation AFTER shard planning: the partial aggregate (not
        # the original avg form) is what each shard's kernel computes
        sharded, base = ND.rewrite_plan(sharded, catalog,
                                        join_index=join_index)
        report = ShardedDispatchReport(decisions=list(base.decisions),
                                       n_shards=n_shards, axis=axis)
    return sharded, report


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _ParallelArtifact:
    wrapped: Any                     # shard_map-wrapped traced function
    # (table, columns, is_spine) per scan, in argument order
    layout: Tuple[Tuple[str, Tuple[str, ...], bool], ...]
    # build-side join indexes, replicated across the mesh (the build
    # tables are replicated, so their indexes are too)
    index_layout: Tuple[L.JoinIndexSpec, ...]
    avals: Tuple[jax.ShapeDtypeStruct, ...]
    param_specs: Tuple[E.Param, ...]
    out_info: L.StaticInfo
    schema: T.Schema
    pad_to: int
    jax_lowered: Any                 # jax.stages.Lowered
    mesh: Mesh
    axis: str


class ParallelEngine:
    """Sharded whole-query compilation behind the stages API.

    ``lower`` expects a shard-planned plan (``stages.lower_plan`` runs
    :func:`shard_plan` for ``engine="parallel"``; direct callers get a
    default all-device mesh) and traces ONE SPMD program: the
    ``build_callable`` trace runs under ``shard_map`` with the spine
    scan's columns partitioned along the mesh axis and everything else
    replicated, merge/gather collectives included.  AOT like the
    ``compiled`` engine: compilation touches no table data.
    """

    name = "parallel"

    def lower(self, p: PL.Plan, catalog: PL.Catalog,
              param_specs: Tuple[E.Param, ...]) -> _ParallelArtifact:
        node = find_shard_node(p)
        if node is None:  # direct Engine-protocol use: default mesh
            p, _ = shard_plan(p, catalog)
            node = find_shard_node(p)
        mesh, axis, spine = node.mesh, node.axis, node.spine
        pad_to, true_rows = node.pad_to, node.true_rows

        def scan_stream(s: PL.Scan, cols: Dict[str, jnp.ndarray],
                        static: L.StaticInfo) -> L.Stream:
            n = next(iter(cols.values())).shape[0]
            mask = None
            if s is spine:
                # padded rows masked off via the global row index
                shard_i = jax.lax.axis_index(axis)
                gidx = shard_i * n + jnp.arange(n, dtype=jnp.int32)
                mask = gidx < np.int32(true_rows)
            return L.Stream(cols, mask, L.StaticInfo(static.cols, n))

        fn, id_layout, index_layout, out_info = L.build_callable(
            p, catalog, param_specs, scan_stream_fn=scan_stream)
        smap = ENG.scan_map(p)
        layout: List[Tuple[str, Tuple[str, ...], bool]] = []
        avals: List[jax.ShapeDtypeStruct] = []
        in_specs: List[P] = []
        for sid, names in id_layout:
            tbl = catalog.table(smap[sid])
            is_spine = sid == id(spine)
            layout.append((smap[sid], tuple(names), is_spine))
            n = pad_to if is_spine else tbl.num_rows
            for name in names:
                avals.append(jax.ShapeDtypeStruct(
                    (n,), jax.dtypes.canonicalize_dtype(tbl[name].dtype)))
                in_specs.append(P(axis) if is_spine else P())
        for spec in index_layout:
            # replicated like the build tables they index (the spine is
            # always the probe side, never a build side)
            for shape in spec.arg_shapes(
                    catalog.table(spec.table).num_rows):
                avals.append(jax.ShapeDtypeStruct(shape, jnp.int32))
                in_specs.append(P())
        for s in param_specs:
            avals.append(jax.ShapeDtypeStruct(
                (), jax.dtypes.canonicalize_dtype(T.numpy_dtype(s.dtype))))
            in_specs.append(P())
        schema = p.schema(catalog)
        # everything after the merge/gather is replicated
        out_specs = ({name: P() for name in schema.names}, P())
        wrapped = jax.shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                                out_specs=out_specs, check_vma=False)
        jax_lowered = jax.jit(wrapped).lower(*avals)
        return _ParallelArtifact(wrapped, tuple(layout),
                                 tuple(index_layout), tuple(avals),
                                 tuple(param_specs), out_info, schema,
                                 pad_to, jax_lowered, mesh, axis)

    def compiler_ir(self, artifact: _ParallelArtifact,
                    dialect: Optional[str] = None) -> Any:
        if dialect in (None, "jaxpr"):
            return jax.make_jaxpr(artifact.wrapped)(*artifact.avals)
        return artifact.jax_lowered.compiler_ir(dialect)

    def compile(self, artifact: _ParallelArtifact) -> S.Executor:
        FZ.fault_point("compile.xla", engine="parallel")
        exe = artifact.jax_lowered.compile()
        layout, specs = artifact.layout, artifact.param_specs
        index_layout = artifact.index_layout
        pdtypes = [a.dtype for a in artifact.avals[len(artifact.avals)
                                                   - len(specs):]]
        out_info, schema, pad_to = (artifact.out_info, artifact.schema,
                                    artifact.pad_to)

        # inputs are placed on the mesh once, in the device cache
        row_sharded = NamedSharding(artifact.mesh, P(artifact.axis))
        replicated = NamedSharding(artifact.mesh, P())

        def run(catalog: PL.Catalog, device_cache: ENG.DeviceCache,
                params: Optional[Dict[str, Any]]) -> L.Result:
            args = []
            for tname, names, is_spine in layout:
                tbl = catalog.table(tname)
                for n in names:
                    args.append(
                        device_cache.get_placed(tbl, n, row_sharded, pad_to)
                        if is_spine else
                        device_cache.get_placed(tbl, n, replicated))
            args.extend(device_cache.place(a, replicated) for a in
                        S.index_args(index_layout, catalog, device_cache))
            for s, dt in zip(specs, pdtypes):
                args.append(jnp.asarray(ENG.require_param(params, s), dt))
            out_cols, mask = exe(*args)
            out_np = {k: np.asarray(v) for k, v in out_cols.items()}
            dicts = {n: sc.dictionary for n, sc in out_info.cols.items()}
            return L.Result(out_np, np.asarray(mask), schema, dicts)

        return run


S.register_engine(ParallelEngine())


# ---------------------------------------------------------------------------
# legacy one-shot entry point
# ---------------------------------------------------------------------------


def execute_parallel(p: PL.Plan, catalog: PL.Catalog, mesh: Mesh,
                     axis: str = "data") -> L.Result:
    """One-shot sharded execution (back-compat shim over the stages
    API).  Prepared queries should hold on to
    ``lower_plan(p, catalog, engine="parallel", mesh=mesh).compile()``.
    """
    return S.lower_plan(p, catalog, engine="parallel", mesh=mesh,
                        axis=axis).compile().result()
