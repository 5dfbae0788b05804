"""Explicit compilation stages: ``Query -> Lowered -> Compiled``.

The paper's Flare accelerates Spark by making the compilation pipeline a
first-class object instead of a side effect of ``collect()``.  This module
is that pipeline, shaped after ``jax.stages`` / the JAX AOT API (and the
JaCe ``Wrapped -> Lowered -> Compiled`` reimplementation of it):

    lowered  = df.lower(engine="compiled")   # plan optimized + lowered
    lowered.plan()                           # inspect the optimized plan
    lowered.compiler_ir("stablehlo")         # inspect the compiler IR
    compiled = lowered.compile()             # measured AOT compile
    compiled(**params)                       # execute (many times, cheap)

Separating the stages buys three things the paper's evaluation relies on:

* compile time and run time are measured independently
  (``CompileStats.lower_s`` / ``compile_s`` / ``run_s``),
* one compiled program is reused across executions -- and, with
  :func:`repro.core.expr.param` placeholders, across *parameter bindings*
  (prepared-statement semantics: the binding becomes a traced scalar
  argument instead of a baked-in literal),
* engines are pluggable: anything implementing the :class:`Engine`
  protocol can be registered and driven through the same API
  (DESIGN.md section 4).

All built-in engines (``volcano``, ``stage``, ``compiled``, the
row-interpreted ``tuple`` and the mesh-sharded ``parallel`` engine of
``repro.core.parallel``) run behind this API and return
differentially-comparable :class:`repro.core.lower.Result` objects --
the engine differential matrix (``tests/test_engine_matrix.py``) drives
every registered engine through this one surface.
"""
from __future__ import annotations

import dataclasses
import time
from typing import (Any, Callable, Dict, Iterable, List, Optional, Protocol,
                    Sequence, Tuple)

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import engines as ENG
from repro.core import expr as E
from repro.core import lower as L
from repro.core import plan as P
from repro.obs import trace as OT
from repro.persist import executable as PX
from repro.persist import store as PSTORE
from repro.relational import table as T
from repro.resilience import degrade as DG
from repro.resilience import faults as FZ

CompileStats = ENG.CompileStats

# An executor is catalog-free: it is (re)bound to a catalog + device cache
# at every call, so a CompileCache entry can serve any catalog whose table
# metadata matches the template key.  Relational plans yield a Result;
# IterativeKernel plans yield a ValueResult (the kernel's pytree).
Executor = Callable[[P.Catalog, ENG.DeviceCache, Optional[Dict[str, Any]]],
                    Any]


# ---------------------------------------------------------------------------
# template cache keys + the explicit cache handle
# ---------------------------------------------------------------------------


def template_key(engine: str, p: P.Plan, catalog: P.Catalog,
                 index_specs: Optional[Dict[int, Any]] = None) -> Tuple:
    """Structural cache key of a (engine, plan, table-metadata) template.

    Param placeholders fingerprint structurally (``p:name:dtype``), so two
    bindings of one template share a key; literals are part of the key.
    Dictionary CONTENTS are baked into compiled programs (string-predicate
    LUTs, comparison codes, decode tables), so the key must cover them,
    not just their lengths.  Every key component is process-independent
    (``table.dict_token`` rather than salted builtin ``hash``), because
    the same key also addresses the on-disk artifact store
    (``repro.persist``): process B must compute the digest process A
    wrote under.

    Join-index identity is part of the key: which joins lower against a
    cached build-side index (and over which table/key columns) changes
    the program's argument layout, so an index-served template and an
    argsort template never share an executable -- while every parameter
    binding of one template still does (the index rides as runtime
    arguments, not baked constants).
    """
    parts: List[Any] = [engine, p.fingerprint()]
    for name in sorted(set(ENG.scan_tables(p))):
        tbl = catalog.table(name)
        parts.append((name, tbl.num_rows,
                      tuple((f.name, f.dtype, f.domain, f.unique,
                             T.dict_token(tbl.dictionary(f.name)))
                            for f in tbl.schema)))
    if getattr(p, "_join_index_disabled", False):
        parts.append(("joinidx", "disabled"))
    else:
        if index_specs is None:  # direct callers; lower_plan passes its own
            index_specs, _ = L.join_index_plan(p, catalog)
        parts.append(("joinidx", tuple(
            (s.table, s.key_cols, s.doms, s.masked, s.direct)
            for s in index_specs.values())))
    return tuple(parts)


class CompileCache:
    """Explicit handle on compiled query templates.

    One entry per :func:`template_key`; the entry is a catalog-free
    :data:`Executor`.  ``hits``/``misses`` give the cache-hit rate that
    the benchmarks report.  Batched executors (``Compiled.batch``) live
    in the same cache under the base key extended with ``("batch",
    bucket)`` -- one compile per (template, batch bucket).  Every
    instance registers with :func:`repro.core.engines.cache_stats` for
    the process-wide aggregate view.
    """

    kind = "compile"

    def __init__(self):
        self._entries: Dict[Tuple, Executor] = {}
        self.hits = 0
        self.misses = 0
        ENG.register_cache(self)

    def lookup(self, key: Tuple) -> Optional[Executor]:
        exe = self._entries.get(key)
        if exe is None:
            self.misses += 1
        else:
            self.hits += 1
        return exe

    def insert(self, key: Tuple, exe: Executor) -> None:
        self._entries[key] = exe

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: Tuple) -> bool:
        return key in self._entries

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def clear(self) -> None:
        self._entries.clear()
        self.hits = 0
        self.misses = 0


_DEFAULT_COMPILE_CACHE = CompileCache()


# ---------------------------------------------------------------------------
# the persistent store tier under the CompileCache (DESIGN.md section 12)
# ---------------------------------------------------------------------------


def _resolve_store(persist: Any, device_cache: ENG.DeviceCache
                   ) -> Optional["PSTORE.ArtifactStore"]:
    """The store governing one compile: ``persist=False`` disables,
    an :class:`repro.persist.ArtifactStore` selects explicitly, None
    defers to the device cache's store and then ``$FLARE_CACHE_DIR``."""
    if persist is False:
        return None
    if persist is not None:
        return persist
    return device_cache.indexes._store()


def _exec_digest(key: Tuple, bucket: Optional[int] = None) -> str:
    """Content address of one executable artifact: the (process-
    independent) template key, extended for batched executables with
    the vmap bucket -- mirroring the in-memory CompileCache keying."""
    if bucket is None:
        return PSTORE.stable_digest("exec", key)
    return PSTORE.stable_digest("exec", key, ("batch", bucket))


def _persistable(engine_name: str, p: P.Plan) -> Tuple[bool, str]:
    if engine_name not in PX.PERSISTABLE_ENGINES:
        return False, (f"engine {engine_name!r} has no serializable "
                       f"whole-query executable")
    return PX.plan_persistable(p)


def _template_geometry(p: P.Plan, catalog: P.Catalog
                       ) -> Tuple[Tuple[Tuple[str, Tuple[str, ...]], ...],
                                  Tuple[L.JoinIndexSpec, ...]]:
    """The argument geometry of a template WITHOUT tracing it: the scan
    (table, columns) layout in trace-argument order and the join-index
    layout.  Pure function of (plan, catalog) -- it recomputes exactly
    what :func:`repro.core.lower.build_callable` would hand back, which
    is what lets a store-loaded executable re-bind its arguments in a
    process that never traced the plan."""
    needed = L.required_scan_columns(p, catalog)
    smap = ENG.scan_map(p)
    order: List[P.Plan] = []

    def collect(n: P.Plan):
        if isinstance(n, P.Scan):
            order.append(n)
        for c in n.children():
            collect(c)

    collect(p)
    layout = tuple((smap[id(s)], tuple(needed[id(s)])) for s in order)
    if getattr(p, "_join_index_disabled", False):
        index_layout: Tuple[L.JoinIndexSpec, ...] = ()
    else:
        specs, _ = L.join_index_plan(p, catalog)
        index_layout = tuple(specs.values())
    return layout, index_layout


def _load_persisted_exec(store: "PSTORE.ArtifactStore", digest: str,
                         p: P.Plan, catalog: P.Catalog, engine_name: str,
                         param_specs: Tuple[E.Param, ...],
                         bucket: Optional[int] = None
                         ) -> Tuple[Optional[Any], str]:
    """Deserialize one executable artifact into a ready executor.

    Tier order inside the artifact: the **native** payload (a
    serialized PjRt executable -- loads in milliseconds with ZERO XLA
    compilation) requires a full version-envelope match; the
    **portable** ``jax.export`` payload survives toolchain drift but
    re-pays the XLA compile.  Anything structurally off counts
    ``corrupt``; an artifact neither tier can use counts
    ``version_miss``.  Returns ``(executor-or-BatchExecutor, "hit:...")``
    or ``(None, "")`` -- failures always fall back to a fresh compile.
    """
    loaded = store.load("exec", digest, envelope_keys=("format",))
    if loaded is None:
        return None, ""
    header, sections = loaded
    meta = header.get("meta") or {}
    # IterativeKernel roots return a kernel-result pytree, not columns:
    # the "value" kind.  There is no schema; the output tree structure is
    # recovered by an abstract re-trace (jax.eval_shape -- plan lowering
    # runs again, XLA compilation still does not).
    is_value = isinstance(p, P.IterativeKernel)
    layout, index_layout = _template_geometry(p, catalog)
    pdtypes = [jax.dtypes.canonicalize_dtype(T.numpy_dtype(s.dtype))
               for s in param_specs]
    n_args = (sum(len(names) for _, names in layout)
              + sum(s.n_arrays for s in index_layout) + len(param_specs))
    if is_value:
        schema = out_info = None
        try:
            build = (L.build_batch_callable if bucket is not None
                     else L.build_callable)
            fn = build(p, catalog, param_specs)[0]
            avals = shared_avals(layout, index_layout, catalog)
            for s, dt in zip(param_specs, pdtypes):
                avals.append(jax.ShapeDtypeStruct(
                    () if bucket is None else (bucket,), dt))
            out_leaves, out_tree = jax.tree_util.tree_flatten(
                jax.eval_shape(fn, *avals))
        except Exception:
            store.demote_hit("exec", "corrupt")
            return None, ""
        n_out = len(out_leaves)
    else:
        schema = p.schema(catalog)
        out_info = L.static_info(p, catalog)
        out_tree = None
        n_out = len(schema.names) + 1
    expect = {
        "engine": engine_name,
        "bucket": bucket,
        "params": [[s.name, s.dtype] for s in param_specs],
        "n_args": n_args,
        "n_out": n_out,
        "kind": "value" if is_value else "relational",
    }
    if meta.get("kind") is None:  # artifacts written before "kind" existed
        expect.pop("kind")
    if (len(sections) != 2
            or any(meta.get(k) != v for k, v in expect.items())):
        store.demote_hit("exec", "corrupt")
        return None, ""
    if is_value:
        names_sorted, dicts = [], {}
    else:
        # flat output order of the native executable = tree_flatten of the
        # traced (out_cols dict, mask) pytree: sorted column names, then
        # mask
        names_sorted = sorted(schema.names)
        dicts = {n: sc.dictionary for n, sc in out_info.cols.items()}

    dispatch: Optional[Callable[[List[Any]], Any]] = None
    disposition = ""
    if sections[0] and header.get("envelope") == store.current_envelope():
        try:
            native = PX.deserialize_native(sections[0])
            kept = tuple(int(i) for i in meta.get("kept", []))

            if is_value:
                def dispatch(args, _native=native, _kept=kept):
                    outs = PX.execute_flat(_native, args, _kept)
                    return jax.tree_util.tree_unflatten(out_tree, outs)
            else:
                def dispatch(args, _native=native, _kept=kept):
                    outs = PX.execute_flat(_native, args, _kept)
                    return (dict(zip(names_sorted, outs)),
                            outs[len(names_sorted)])

            disposition = "hit:native"
        except Exception:
            dispatch = None
    if dispatch is None and sections[1] and \
            store.current_envelope()["platform"] in (meta.get("platforms")
                                                     or []):
        try:
            exe = PX.deserialize_portable(sections[1])

            def dispatch(args, _exe=exe):
                return _exe(*args)

            disposition = "hit:portable"
        except Exception:
            dispatch = None
    if dispatch is None:
        store.demote_hit("exec", "version_miss")
        return None, ""

    if bucket is None:
        def raw(catalog_: P.Catalog, device_cache: ENG.DeviceCache,
                params: Optional[Dict[str, Any]]):
            args = _marshal_args(layout, index_layout, catalog_,
                                 device_cache)
            for s, dt in zip(param_specs, pdtypes):
                args.append(jnp.asarray(ENG.require_param(params, s), dt))
            return dispatch(args)

        def finalize(out):
            if schema is None:  # value kind: kernel result pytree
                return L.ValueResult(jax.tree_util.tree_map(np.asarray,
                                                            out))
            out_cols, mask = out
            out_np = {k: np.asarray(v) for k, v in out_cols.items()}
            return L.Result(out_np, np.asarray(mask), schema, dicts)

        def run(catalog_: P.Catalog, device_cache: ENG.DeviceCache,
                params: Optional[Dict[str, Any]]):
            return finalize(raw(catalog_, device_cache, params))

        run.raw = raw
        run.finalize = finalize
        return run, disposition

    def braw(catalog_: P.Catalog, device_cache: ENG.DeviceCache,
             stacked: Dict[str, np.ndarray]):
        args = _marshal_args(layout, index_layout, catalog_, device_cache)
        for s, dt in zip(param_specs, pdtypes):
            args.append(jnp.asarray(stacked[s.name], dt))
        return dispatch(args)

    def finalize_one(out, i: int):
        if schema is None:  # value kind: kernel pytree stacked on axis 0
            return L.ValueResult(jax.tree_util.tree_map(
                lambda v: np.asarray(v[i]), out))
        out_cols, mask = out
        out_np = {k: np.asarray(v[i]) for k, v in out_cols.items()}
        return L.Result(out_np, np.asarray(mask[i]), schema, dicts)

    return BatchExecutor(braw, finalize_one, bucket), disposition


def _save_persisted_exec(store: "PSTORE.ArtifactStore", digest: str,
                         exe_like: Any, engine_name: str,
                         param_specs: Tuple[E.Param, ...],
                         schema: Optional[T.Schema],
                         bucket: Optional[int] = None) -> str:
    """Write-through after a fresh compile.  Serializes both payload
    tiers (native PjRt bytes; portable ``jax.export`` bytes, best
    effort) under the artifact's content digest.  Never raises: any
    failure is counted and the compile result stands."""
    jax_exe = getattr(exe_like, "jax_exe", None)
    export_src = getattr(exe_like, "export_src", None)
    n_args = getattr(exe_like, "n_args", None)
    n_out = getattr(exe_like, "n_out", None)
    is_value = schema is None
    if jax_exe is None or n_args is None or (is_value and n_out is None):
        store.tier("exec").unsupported += 1
        return "unsupported: executor exposes no serializable executable"
    try:
        native_bytes, kept = PX.serialize_compiled(jax_exe)
    except Exception as e:
        store.tier("exec").errors += 1
        return f"error: {type(e).__name__}"
    exported, platforms = b"", []
    if export_src is not None:
        try:
            exported, platforms = PX.export_portable(*export_src)
        except Exception:
            pass  # the portable tier is optional; native alone still serves
    meta = {
        "engine": engine_name,
        "bucket": bucket,
        "params": [[s.name, s.dtype] for s in param_specs],
        "n_args": n_args,
        "n_out": n_out if is_value else len(schema.names) + 1,
        "kind": "value" if is_value else "relational",
        "kept": list(kept),
        "platforms": platforms,
    }
    path = store.save("exec", digest, meta, [native_bytes, exported])
    return "written" if path else "error: write failed"


def bind_params(p: P.Plan, params: Dict[str, Any]) -> P.Plan:
    """Substitute Param placeholders with literal values (plan rewrite).

    Used by purely interpreted engines (``tuple``), where there is no
    compiled artifact to share; also handy for explain()-ing a template
    at a concrete binding.
    """

    def sub(e: E.Expr) -> Optional[E.Expr]:
        if isinstance(e, E.Param):
            return E.Lit(ENG.require_param(params, e))
        return None

    def rule(n: P.Plan) -> Optional[P.Plan]:
        if isinstance(n, P.Filter):
            return P.Filter(n.child, E.map_expr(n.pred, sub))
        if isinstance(n, P.Project):
            return P.Project(n.child, tuple(
                (name, E.map_expr(e, sub)) for name, e in n.outputs))
        if isinstance(n, P.Aggregate):
            return P.Aggregate(n.child, n.keys, tuple(
                dataclasses.replace(a, arg=E.map_expr(a.arg, sub))
                if a.arg is not None else a for a in n.aggs))
        if isinstance(n, P.IterativeKernel):
            return P.IterativeKernel(n.child, n.kernel, n.features, n.label,
                                     tuple((k, ENG.require_param(params, v)
                                            if isinstance(v, E.Param) else v)
                                           for k, v in n.hyper))
        return None

    return P.transform(p, rule)


# ---------------------------------------------------------------------------
# the Engine protocol + registry
# ---------------------------------------------------------------------------


class Engine(Protocol):
    """A pluggable execution back-end behind the stages API.

    ``lower`` turns an optimized plan into an engine-specific artifact
    (traced program, stage decomposition, ...); ``compile`` turns that
    artifact into a reusable catalog-free :data:`Executor`;
    ``compiler_ir`` exposes the artifact for inspection.
    """

    name: str

    def lower(self, p: P.Plan, catalog: P.Catalog,
              param_specs: Tuple[E.Param, ...]) -> Any:
        """Lower ``p``; returns the engine's lowering artifact."""
        ...

    def compiler_ir(self, artifact: Any, dialect: Optional[str] = None) -> Any:
        """Inspect the lowering artifact in the requested dialect."""
        ...

    def compile(self, artifact: Any) -> Executor:
        """Compile the artifact into an executor."""
        ...


ENGINES: Dict[str, Engine] = {}


def register_engine(engine: Engine) -> Engine:
    """Register a back-end under ``engine.name`` (last wins)."""
    ENGINES[engine.name] = engine
    return engine


def get_engine(name: str) -> Engine:
    try:
        return ENGINES[name]
    except KeyError:
        raise ValueError(f"unknown engine {name!r}; available: "
                         f"{available_engines()}") from None


def available_engines() -> List[str]:
    return sorted(ENGINES)


# ---------------------------------------------------------------------------
# whole-query engine (Flare Level 2): ONE XLA program, AOT-compiled
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _WholeQueryArtifact:
    fn: Callable
    # (table_name, column_names) per scan, in argument order
    layout: Tuple[Tuple[str, Tuple[str, ...]], ...]
    # cached build-side join indexes: the arrays of each spec's
    # arg_shapes, between the scan columns and the params (DESIGN.md
    # sec. 10)
    index_layout: Tuple[L.JoinIndexSpec, ...]
    avals: Tuple[jax.ShapeDtypeStruct, ...]
    param_specs: Tuple[E.Param, ...]
    # None for IterativeKernel roots: the program returns a kernel
    # result pytree, not relational columns
    out_info: Optional[L.StaticInfo]
    schema: Optional[T.Schema]
    jax_lowered: Any  # jax.stages.Lowered


def index_args(index_layout: Tuple[L.JoinIndexSpec, ...],
               catalog: P.Catalog, device_cache: ENG.DeviceCache
               ) -> List[jnp.ndarray]:
    """Fetch the index arrays for an executable's join-index layout from
    the device cache: (perm, sorted keys) per join, plus the slot table
    of a direct join (built on first use, then device-resident -- the
    IndexCache hit-rate telemetry counts this)."""
    args: List[jnp.ndarray] = []
    for spec in index_layout:
        idx = device_cache.get_index(catalog.table(spec.table),
                                     spec.key_cols, spec.doms)
        args.append(idx.perm)
        args.append(idx.keys)
        if spec.direct:
            args.append(idx.slot_table(spec.domain))
    return args


def shared_avals(layout: Tuple[Tuple[str, Tuple[str, ...]], ...],
                 index_layout: Sequence[L.JoinIndexSpec],
                 catalog: P.Catalog) -> List[jax.ShapeDtypeStruct]:
    """Avals of a template's binding-independent arguments: the scan
    columns then the join-index arrays.  Shared between the
    single-binding and the vmap-batched lowering -- the batched program
    broadcasts exactly these and stacks only the params."""
    avals: List[jax.ShapeDtypeStruct] = []
    for tname, names in layout:
        tbl = catalog.table(tname)
        for n in names:
            avals.append(jax.ShapeDtypeStruct(
                (tbl.num_rows,),
                jax.dtypes.canonicalize_dtype(tbl[n].dtype)))
    for spec in index_layout:
        for shape in spec.arg_shapes(catalog.table(spec.table).num_rows):
            avals.append(jax.ShapeDtypeStruct(shape, jnp.int32))
    return avals


def _marshal_args(layout: Tuple[Tuple[str, Tuple[str, ...]], ...],
                  index_layout: Sequence[L.JoinIndexSpec],
                  catalog: P.Catalog, device_cache: ENG.DeviceCache
                  ) -> List[jnp.ndarray]:
    """The binding-independent argument prefix of a whole-query
    executable: device-resident scan columns (in layout order) followed
    by the join-index arrays.  Shared by freshly-compiled
    and store-loaded executors -- the layout is a pure function of
    (plan, catalog), which is what lets a deserialized executable be
    re-bound to arguments without ever tracing."""
    args: List[jnp.ndarray] = []
    for tname, names in layout:
        tbl = catalog.table(tname)
        for n in names:
            args.append(device_cache.get(tbl, n))
    args.extend(index_args(index_layout, catalog, device_cache))
    return args


class WholeQueryEngine:
    """Whole-query compilation: plan -> one jaxpr -> one XLA executable.

    The AOT path: lowering traces against ``ShapeDtypeStruct`` avals
    derived from the catalog (row counts + dtypes are static), so
    ``compile()`` needs no data at all.
    """

    name = "compiled"

    def lower(self, p: P.Plan, catalog: P.Catalog,
              param_specs: Tuple[E.Param, ...]) -> _WholeQueryArtifact:
        fn, id_layout, index_layout, out_info = L.build_callable(
            p, catalog, param_specs)
        smap = ENG.scan_map(p)
        layout = tuple((smap[sid], tuple(names)) for sid, names in id_layout)
        avals = shared_avals(layout, index_layout, catalog)
        for s in param_specs:
            avals.append(jax.ShapeDtypeStruct(
                (), jax.dtypes.canonicalize_dtype(T.numpy_dtype(s.dtype))))
        jax_lowered = jax.jit(fn).lower(*avals)
        schema = (None if isinstance(p, P.IterativeKernel)
                  else p.schema(catalog))
        return _WholeQueryArtifact(fn, layout, tuple(index_layout),
                                   tuple(avals), param_specs,
                                   out_info, schema, jax_lowered)

    def compiler_ir(self, artifact: _WholeQueryArtifact,
                    dialect: Optional[str] = None) -> Any:
        if dialect in (None, "jaxpr"):
            return jax.make_jaxpr(artifact.fn)(*artifact.avals)
        return artifact.jax_lowered.compiler_ir(dialect)

    def compile(self, artifact: _WholeQueryArtifact) -> Executor:
        FZ.fault_point("compile.xla")
        exe = artifact.jax_lowered.compile()
        layout, specs = artifact.layout, artifact.param_specs
        index_layout = artifact.index_layout
        pdtypes = [a.dtype for a in artifact.avals[len(artifact.avals)
                                                   - len(specs):]]
        out_info, schema = artifact.out_info, artifact.schema

        def raw(catalog: P.Catalog, device_cache: ENG.DeviceCache,
                params: Optional[Dict[str, Any]]):
            """Dispatch only: returns the (possibly un-synced) device
            output pytree -- the deferred-readiness path behind
            ``Compiled.submit`` / ``__call__(block=False)``."""
            args = _marshal_args(layout, index_layout, catalog,
                                 device_cache)
            for s, dt in zip(specs, pdtypes):
                args.append(jnp.asarray(ENG.require_param(params, s), dt))
            return exe(*args)

        def finalize(out):
            if schema is None:  # heterogeneous pipeline: kernel pytree
                return L.ValueResult(jax.tree_util.tree_map(np.asarray,
                                                            out))
            out_cols, mask = out
            out_np = {k: np.asarray(v) for k, v in out_cols.items()}
            dicts = {n: sc.dictionary for n, sc in out_info.cols.items()}
            return L.Result(out_np, np.asarray(mask), schema, dicts)

        def run(catalog: P.Catalog, device_cache: ENG.DeviceCache,
                params: Optional[Dict[str, Any]]):
            return finalize(raw(catalog, device_cache, params))

        run.raw = raw            # deferred-sync protocol (AsyncResult)
        run.finalize = finalize
        # handles for the persistent store tier (repro.persist): the
        # jax executable to serialize, its argument count, flat output
        # arity, and the (fn, avals) source for the portable jax.export
        # payload
        run.jax_exe = exe
        run.n_args = len(artifact.avals)
        try:
            run.n_out = jax.tree_util.tree_structure(
                artifact.jax_lowered.out_info).num_leaves
        except Exception:
            run.n_out = None
        run.export_src = (artifact.fn, artifact.avals)
        return run


# ---------------------------------------------------------------------------
# stage-granular engine (Spark/Tungsten analogue)
# ---------------------------------------------------------------------------


def stage_decomposition(p: P.Plan) -> List[P.Plan]:
    """Stage roots in bottom-up execution order (the Lowered IR of the
    ``stage`` engine): every pipeline breaker below another stage root
    starts its own stage, mirroring ``engines.StageEngine``."""
    out: List[P.Plan] = []

    def gather(root: P.Plan):
        def rec(n: P.Plan, is_root: bool):
            if isinstance(n, ENG._BREAKERS) and not is_root:
                gather(n)
                return
            for c in n.children():
                rec(c, False)

        rec(root, True)
        out.append(root)

    gather(p)
    return out


@dataclasses.dataclass
class _StageArtifact:
    plan: P.Plan
    stages: List[P.Plan]
    param_specs: Tuple[E.Param, ...]


class StagePipelineEngine:
    """Stage-granular compilation: one jit per pipeline breaker, host
    round-trips between stages.  Per-stage XLA compiles happen lazily on
    the first execution (stage shapes depend on materialised masks), so
    ``compile_s`` covers pipeline assembly and the first run pays the
    residual jit cost -- exactly the Spark-runtime behaviour the paper's
    Fig. 5/6 measures."""

    name = "stage"

    def lower(self, p: P.Plan, catalog: P.Catalog,
              param_specs: Tuple[E.Param, ...]) -> _StageArtifact:
        return _StageArtifact(p, stage_decomposition(p), param_specs)

    def compiler_ir(self, artifact: _StageArtifact,
                    dialect: Optional[str] = None) -> Any:
        if dialect in (None, "stages"):
            return [s.explain() for s in artifact.stages]
        raise ValueError(f"unknown dialect {dialect!r} for stage engine "
                         "(use 'stages')")

    def compile(self, artifact: _StageArtifact) -> Executor:
        eng = ENG.StageEngine()  # its jit cache lives with this executor

        def run(catalog: P.Catalog, device_cache: ENG.DeviceCache,
                params: Optional[Dict[str, Any]]) -> L.Result:
            return eng.execute(artifact.plan, catalog, device_cache, params)

        return run


# ---------------------------------------------------------------------------
# interpreted engines (volcano oracle + tuple-at-a-time baseline)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _InterpArtifact:
    plan: P.Plan
    param_specs: Tuple[E.Param, ...]


class VolcanoStageEngine:
    """Vectorised interpreter (the correctness oracle).  ``lower`` is the
    identity on the optimized plan and ``compile`` wraps an interpreter
    -- the stages API still applies, compile just measures ~0."""

    name = "volcano"

    def lower(self, p: P.Plan, catalog: P.Catalog,
              param_specs: Tuple[E.Param, ...]) -> _InterpArtifact:
        return _InterpArtifact(p, param_specs)

    def compiler_ir(self, artifact: _InterpArtifact,
                    dialect: Optional[str] = None) -> Any:
        return artifact.plan.explain()

    def compile(self, artifact: _InterpArtifact) -> Executor:
        def run(catalog: P.Catalog, device_cache: ENG.DeviceCache,
                params: Optional[Dict[str, Any]]) -> L.Result:
            return ENG.VolcanoEngine().execute(artifact.plan, catalog,
                                               None, params)

        return run


class TupleStageEngine:
    """Row-at-a-time Volcano baseline.  Params are bound by plan rewrite
    (Param -> Lit) per execution: with no compiled artifact there is
    nothing to share, so substitution IS prepared-statement execution."""

    name = "tuple"

    def lower(self, p: P.Plan, catalog: P.Catalog,
              param_specs: Tuple[E.Param, ...]) -> _InterpArtifact:
        return _InterpArtifact(p, param_specs)

    def compiler_ir(self, artifact: _InterpArtifact,
                    dialect: Optional[str] = None) -> Any:
        return artifact.plan.explain()

    def compile(self, artifact: _InterpArtifact) -> Executor:
        from repro.core.tuple_engine import TupleEngine

        def run(catalog: P.Catalog, device_cache: ENG.DeviceCache,
                params: Optional[Dict[str, Any]]) -> L.Result:
            p = artifact.plan
            if artifact.param_specs:
                p = bind_params(p, params)
            return TupleEngine().execute(p, catalog)

        return run


for _cls in (WholeQueryEngine, StagePipelineEngine, VolcanoStageEngine,
             TupleStageEngine):
    register_engine(_cls())


# ---------------------------------------------------------------------------
# the stage objects
# ---------------------------------------------------------------------------


class Lowered:
    """An optimized plan lowered for one engine, awaiting compilation.

    Lowering is forced lazily: ``compile()`` on a cache hit never traces,
    which is what makes prepared-query reuse cheap.  Inspect via
    :meth:`plan`, :meth:`explain` and :meth:`compiler_ir`.
    """

    def __init__(self, p: P.Plan, catalog: P.Catalog, engine: Engine,
                 param_specs: Tuple[E.Param, ...], key: Tuple,
                 device_cache: ENG.DeviceCache,
                 compile_cache: CompileCache,
                 dispatch_report: Optional[Any] = None):
        self._plan = p
        self._catalog = catalog
        self._engine = engine
        self._param_specs = param_specs
        self._key = key
        self._device_cache = device_cache
        self._compile_cache = compile_cache
        self._dispatch_report = dispatch_report
        self._artifact: Any = None
        self._lower_s = 0.0
        # re-lower source for the degradation ladder: the pre-rewrite
        # plan + lowering kwargs, stashed by lower_plan().  None for
        # directly-constructed Lowered objects (no ladder).
        self._degrade_src: Optional[Dict[str, Any]] = None

    # -- introspection -------------------------------------------------------

    @property
    def engine_name(self) -> str:
        return self._engine.name

    @property
    def cache_key(self) -> Tuple:
        return self._key

    def plan(self) -> P.Plan:
        """The optimized logical/physical plan this template lowers."""
        return self._plan

    def explain(self) -> str:
        return "== Physical Plan ==\n" + self._plan.explain()

    def params(self) -> Tuple[E.Param, ...]:
        """Param placeholders (sorted by name = binding order)."""
        return self._param_specs

    def dispatch_report(self) -> Optional[Any]:
        """Native kernel dispatch report
        (:class:`repro.native.registry.DispatchReport`): which patterns
        fired and which fragments fell back -- populated by
        ``native=True`` / ``compiled-native``.  Its ``index_decisions``
        name, per join, whether the build side probes the cached join
        index or rebuilds in-program (present for any compiled/parallel
        template with joins).  None for interpreted engines and for
        join-free non-native templates."""
        return self._dispatch_report

    def compiler_ir(self, dialect: Optional[str] = None) -> Any:
        """Engine IR: jaxpr/stablehlo (compiled), stage list (stage),
        plan text (interpreters)."""
        return self._engine.compiler_ir(self._force(), dialect)

    # -- the next stage ------------------------------------------------------

    def _force(self) -> Any:
        if self._artifact is None:
            with OT.span("lower", engine=self._engine.name):
                t0 = time.perf_counter()
                self._artifact = self._engine.lower(
                    self._plan, self._catalog, self._param_specs)
                self._lower_s = time.perf_counter() - t0
        return self._artifact

    def compile(self, cache: Optional[CompileCache] = None,
                persist: Any = None) -> "Compiled":
        """Compile (or fetch) the executable for this template; returns
        a :class:`Compiled` with fresh CompileStats.

        Lookup order: memory (``cache``), then the persistent store
        tier -- ``persist`` names an :class:`repro.persist.
        ArtifactStore`, ``False`` disables the disk tier, None (the
        default) uses the context's store and then the ambient
        ``$FLARE_CACHE_DIR``.  A disk hit deserializes, promotes to
        memory, and sets ``stats.disk_hit`` (no tracing, and on the
        native tier no XLA compilation); a fresh compile writes
        through.

        Failures on the recoverable allowlist (kernel budget, corrupt
        artifact, XLA compile error -- :func:`repro.resilience.degrade.
        recoverable`) re-lower on the next rung of the degradation
        ladder instead of raising, recording the hop on
        ``stats.degraded``; ``FLARE_DEGRADE=off`` disables this.
        """
        try:
            return self._compile_inner(cache, persist)
        except Exception as err:
            low, event = DG.next_lowered(self._degrade_src,
                                         self._engine.name, err, "compile")
            if low is None:
                raise
            compiled = low.compile(persist=persist)
            compiled.stats.degraded = ((event.to_dict(),)
                                       + tuple(compiled.stats.degraded))
            return compiled

    def _compile_inner(self, cache: Optional[CompileCache],
                       persist: Any) -> "Compiled":
        cache = cache if cache is not None else self._compile_cache
        stats = CompileStats(engine=self._engine.name, cache_key=self._key,
                             dispatch=self._dispatch_report)
        store = _resolve_store(persist, self._device_cache)
        with OT.span("compile", engine=self._engine.name) as csp:
            exe = cache.lookup(self._key)
            if exe is None:
                can_persist = False
                if store is not None:
                    can_persist, reason = _persistable(self._engine.name,
                                                       self._plan)
                    if can_persist:
                        with OT.span("persist", op="load") as psp:
                            t0 = time.perf_counter()
                            exe, disposition = _load_persisted_exec(
                                store, _exec_digest(self._key),
                                self._plan, self._catalog,
                                self._engine.name, self._param_specs)
                            psp.set(outcome=disposition
                                    if exe is not None else "miss")
                        if exe is not None:
                            stats.compile_s = time.perf_counter() - t0
                            stats.disk_hit = True
                            stats.persist = disposition
                            cache.insert(self._key, exe)
                    else:
                        store.tier("exec").unsupported += 1
                        stats.persist = f"unsupported: {reason}"
                if exe is None:
                    artifact = self._force()
                    t0 = time.perf_counter()
                    exe = self._engine.compile(artifact)
                    stats.compile_s = time.perf_counter() - t0
                    stats.lower_s = self._lower_s
                    cache.insert(self._key, exe)
                    if store is not None and can_persist:
                        with OT.span("persist", op="save") as psp:
                            stats.persist = _save_persisted_exec(
                                store, _exec_digest(self._key), exe,
                                self._engine.name, self._param_specs,
                                getattr(artifact, "schema", None))
                            psp.set(outcome=stats.persist)
            else:
                stats.cache_hit = True
            stats.trace_compile_s = stats.lower_s + stats.compile_s
            csp.set(cache="hit" if stats.cache_hit else "miss",
                    disk="hit" if stats.disk_hit else "miss",
                    compile_s=round(stats.compile_s, 6),
                    lower_s=round(stats.lower_s, 6))
            if stats.persist:
                csp.set(persist=stats.persist)
        return Compiled(exe, self._plan, self._catalog, self._engine.name,
                        self._param_specs, self._key, self._device_cache,
                        stats, compile_cache=cache, store=store,
                        degrade_src=self._degrade_src)


class AsyncResult:
    """A dispatched execution whose device output has NOT been synced.

    Returned by ``Compiled.submit`` / ``Compiled(..., block=False)`` and
    by ``Compiled.batch(block=False)``: the XLA dispatch has happened,
    but no ``jax.block_until_ready`` / host transfer -- readiness is
    deferred until the caller asks for the value.  This is what lets a
    server sync per *request* instead of per batch: every request of a
    coalesced batch holds its own handle onto the shared device output
    and pays the transfer for its own slice only when its client reads.

    ``result()`` materialises (and caches) the host-side
    :class:`repro.core.lower.Result`; ``ready()`` is a non-blocking
    readiness probe; ``block_until_ready()`` waits on the device
    computation without transferring.
    """

    def __init__(self, out: Any, finalize: Callable[[Any], Any]):
        self._out = out
        self._finalize = finalize
        self._result: Any = None
        self._done = False

    def ready(self) -> bool:
        """True once the device computation has finished (non-blocking
        where the runtime exposes readiness; conservatively True after
        any materialisation)."""
        if self._done:
            return True
        for leaf in jax.tree_util.tree_leaves(self._out):
            probe = getattr(leaf, "is_ready", None)
            if probe is not None and not probe():
                return False
        return True

    def block_until_ready(self) -> "AsyncResult":
        if not self._done:
            jax.block_until_ready(self._out)
        return self

    def result(self) -> Any:
        """The host-side Result (blocks until ready, cached)."""
        if not self._done:
            self._result = self._finalize(self._out)
            self._done = True
            self._out = None  # free the device reference
        return self._result

    def compact(self) -> Dict[str, np.ndarray]:
        return self.result().compact()

    collect = compact

    def __repr__(self):
        state = "ready" if self._done or self.ready() else "pending"
        return f"AsyncResult<{state}>"


@dataclasses.dataclass
class BatchExecutor:
    """A compiled vmap-coalesced template: ONE program serving a
    ``bucket``-sized stack of parameter bindings (DESIGN.md section 11).

    Lives in the :class:`CompileCache` under the template's base key
    extended with ``("batch", bucket)``.  ``raw`` dispatches the whole
    batch (stacked ``[bucket]`` param arrays, shared scan/index args)
    and returns the un-synced device output; ``finalize_one(out, i)``
    materialises request ``i``'s slice.
    """

    raw: Callable[[P.Catalog, ENG.DeviceCache, Dict[str, np.ndarray]], Any]
    finalize_one: Callable[[Any, int], Any]
    bucket: int
    # persistent-store handles (None for store-loaded executors, which
    # have nothing new to write back)
    jax_exe: Any = None
    n_args: Optional[int] = None
    n_out: Optional[int] = None
    export_src: Optional[Tuple[Callable, Tuple]] = None


def compile_batch_executor(p: P.Plan, catalog: P.Catalog,
                           param_specs: Tuple[E.Param, ...],
                           bucket: int) -> BatchExecutor:
    """AOT-compile the ``bucket``-wide batched executable of a template.

    The single-binding traced function is vmapped over the param axis
    (:func:`repro.core.lower.build_batch_callable`): scan columns and
    join-index args broadcast (``in_axes=None``), each ``param()``
    placeholder becomes one stacked ``[bucket]`` argument.
    """
    bfn, id_layout, index_layout, out_info = L.build_batch_callable(
        p, catalog, param_specs)
    smap = ENG.scan_map(p)
    layout = tuple((smap[sid], tuple(names)) for sid, names in id_layout)
    avals = shared_avals(layout, index_layout, catalog)
    pdtypes = []
    for s in param_specs:
        dt = jax.dtypes.canonicalize_dtype(T.numpy_dtype(s.dtype))
        pdtypes.append(dt)
        avals.append(jax.ShapeDtypeStruct((bucket,), dt))
    FZ.fault_point("compile.xla", bucket=bucket)
    lowered = jax.jit(bfn).lower(*avals)
    exe = lowered.compile()
    try:
        n_out = jax.tree_util.tree_structure(lowered.out_info).num_leaves
    except Exception:
        n_out = None
    schema = (None if isinstance(p, P.IterativeKernel)
              else p.schema(catalog))

    def raw(catalog: P.Catalog, device_cache: ENG.DeviceCache,
            stacked: Dict[str, np.ndarray]):
        args = _marshal_args(layout, index_layout, catalog, device_cache)
        for s, dt in zip(param_specs, pdtypes):
            args.append(jnp.asarray(stacked[s.name], dt))
        return exe(*args)

    def finalize_one(out, i: int):
        if schema is None:  # heterogeneous root: kernel pytree, axis 0
            return L.ValueResult(jax.tree_util.tree_map(
                lambda v: np.asarray(v[i]), out))
        out_cols, mask = out
        out_np = {k: np.asarray(v[i]) for k, v in out_cols.items()}
        dicts = {n: sc.dictionary for n, sc in out_info.cols.items()}
        return L.Result(out_np, np.asarray(mask[i]), schema, dicts)

    return BatchExecutor(raw, finalize_one, bucket,
                         jax_exe=exe, n_args=len(avals), n_out=n_out,
                         export_src=(bfn, tuple(avals)))


#: Engines whose Compiled objects support vmap-coalesced batching.  The
#: native/parallel variants keep per-binding dispatch: Pallas kernels
#: and shard_map programs do not carry vmap batching rules.
_BATCHABLE_ENGINES = ("compiled",)


class Compiled:
    """An executable query template: call it with parameter bindings.

    ``compiled(**params)`` returns compacted host columns;
    ``compiled.result(**params)`` the raw padded :class:`Result`;
    ``compiled(block=False, **params)`` / ``compiled.submit(**params)``
    an :class:`AsyncResult` whose device arrays are un-synced until
    read.  ``compiled.batch([...bindings...])`` coalesces many bindings
    into ONE vmapped program (DESIGN.md section 11).  One Compiled
    serves any number of bindings without recompilation.
    """

    def __init__(self, exe: Executor, p: P.Plan, catalog: P.Catalog,
                 engine_name: str, param_specs: Tuple[E.Param, ...],
                 key: Tuple, device_cache: ENG.DeviceCache,
                 stats: CompileStats,
                 compile_cache: Optional[CompileCache] = None,
                 store: Optional["PSTORE.ArtifactStore"] = None,
                 degrade_src: Optional[Dict[str, Any]] = None):
        self._exe = exe
        self._plan = p
        self._catalog = catalog
        self.engine_name = engine_name
        self._param_specs = param_specs
        self.cache_key = key
        self._device_cache = device_cache
        self.stats = stats
        self._compile_cache = compile_cache
        self._store = store
        self._last_trace: Optional[OT.Trace] = None
        self._degrade_src = degrade_src
        # sticky execution-time fallback: set by the first recoverable
        # execution failure, every later call routes straight to it
        self._degraded_to: Optional["Compiled"] = None

    def params(self) -> Tuple[E.Param, ...]:
        return self._param_specs

    def last_trace(self) -> Optional[OT.Trace]:
        """The :class:`repro.obs.trace.Trace` of this template's most
        recent execution -- the execute span plus everything recorded
        inside it (batch compiles, store I/O, index lookups).  None
        until an execution runs with tracing enabled (``FLARE_TRACE=1``
        or ``repro.obs.capture()``)."""
        return self._last_trace

    def _check_bindings(self, params: Dict[str, Any]) -> None:
        known = {s.name for s in self._param_specs}
        extra = sorted(set(params) - known)
        if extra:
            raise TypeError(f"unknown parameter(s) {extra}; this template "
                            f"takes {sorted(known)}")

    def _degrade_for(self, err: BaseException) -> Optional["Compiled"]:
        """Build (and pin) the execution-time fallback Compiled for a
        recoverable failure; None when the ladder must not engage."""
        low, event = DG.next_lowered(self._degrade_src, self.engine_name,
                                     err, "execute")
        if low is None:
            return None
        fb = low.compile()
        self.stats.degraded = (tuple(self.stats.degraded)
                               + (event.to_dict(),)
                               + tuple(fb.stats.degraded))
        self._degraded_to = fb
        return fb

    def result(self, **params: Any) -> L.Result:
        if self._degraded_to is not None:
            return self._degraded_to.result(**params)
        try:
            return self._result_inner(**params)
        except Exception as err:
            fb = self._degrade_for(err)
            if fb is None:
                raise
            return fb.result(**params)

    def _result_inner(self, **params: Any) -> L.Result:
        self._check_bindings(params)
        if not OT.active():  # hot path: zero tracing machinery
            t0 = time.perf_counter()
            out = self._exe(self._catalog, self._device_cache,
                            params or None)
            self.stats.run_s = time.perf_counter() - t0
            return out
        buffered = OT.TRACER.on
        mark = OT.TRACER.watermark() if buffered else 0
        with OT.span("execute", engine=self.engine_name,
                     mode="sync") as sp:
            t0 = time.perf_counter()
            out = self._exe(self._catalog, self._device_cache,
                            params or None)
            self.stats.run_s = time.perf_counter() - t0
            sp.set(run_s=round(self.stats.run_s, 6))
            try:
                sp.set(rows=out.num_rows())
            except Exception:
                pass
        if buffered:
            self._last_trace = OT.Trace(OT.TRACER.since(mark))
        return out

    def submit(self, **params: Any) -> AsyncResult:
        """Dispatch without syncing: returns an :class:`AsyncResult`
        whose device arrays stay un-synced until ``.result()`` /
        ``.compact()``.  ``stats.run_s`` then measures dispatch only.
        Engines without a deferred path (interpreters, stage, parallel)
        fall back to eager execution behind an already-ready handle, so
        the API is uniform across engines."""
        if self._degraded_to is not None:
            return self._degraded_to.submit(**params)
        try:
            return self._submit_inner(**params)
        except Exception as err:
            fb = self._degrade_for(err)
            if fb is None:
                raise
            return fb.submit(**params)

    def _submit_inner(self, **params: Any) -> AsyncResult:
        self._check_bindings(params)
        raw = getattr(self._exe, "raw", None)
        tracing = OT.TRACER.on
        mark = OT.TRACER.watermark() if tracing else 0
        with OT.span("execute", engine=self.engine_name,
                     mode="dispatch") as sp:
            t0 = time.perf_counter()
            if raw is None:  # no deferred path: eager, trivially ready
                out = self._exe(self._catalog, self._device_cache,
                                params or None)
                handle = AsyncResult(None, lambda _: out)
                handle.result()
            else:
                out = raw(self._catalog, self._device_cache,
                          params or None)
                handle = AsyncResult(out, self._exe.finalize)
            self.stats.run_s = time.perf_counter() - t0
        if tracing:
            sp.set(run_s=round(self.stats.run_s, 6),
                   deferred=raw is not None)
            self._last_trace = OT.Trace(OT.TRACER.since(mark))
        return handle

    def __call__(self, block: bool = True, **params: Any):
        """Execute one binding.  ``block=True`` (default) returns
        compacted host columns; ``block=False`` returns the un-synced
        :class:`AsyncResult` handle (``.compact()`` when you need the
        rows).  ``block`` is reserved: name a query parameter something
        else, or bind through ``result()``/``submit()``."""
        if not block:
            return self.submit(**params)
        return self.result(**params).compact()

    collect = __call__

    # -- vmap-coalesced multi-binding execution ------------------------------

    def batch(self, bindings: Sequence[Dict[str, Any]],
              block: bool = True) -> List[Any]:
        """Execute many bindings of this template as ONE program.

        The bindings stack into one ``[bucket]`` argument per
        ``param()`` spec (scan columns and join indexes broadcast), the
        vmapped executable runs once, and each binding gets its own
        slice of the shared output: ``block=True`` returns one
        :class:`repro.core.lower.Result` per binding, ``block=False``
        one un-synced :class:`AsyncResult` per binding (the server's
        deferred per-request sync).

        Batched executables are bucketed (:func:`repro.core.engines.
        batch_bucket`: next power of two) and cached in the template's
        CompileCache under ``cache_key + (("batch", bucket),)`` --
        exactly one compile per (template, bucket); ragged batches pad
        by repeating the last binding and the padding is discarded.

        A param-free template degenerates to perfect coalescing: every
        request is the same execution, run once and shared.
        """
        bindings = [dict(b) for b in bindings]
        if not bindings:
            return []
        if self._degraded_to is not None:
            return self._batch_on(self._degraded_to, bindings, block)
        try:
            return self._batch_inner(bindings, block)
        except Exception as err:
            fb = self._degrade_for(err)
            if fb is None:
                raise
            return self._batch_on(fb, bindings, block)

    @staticmethod
    def _batch_on(fb: "Compiled", bindings: List[Dict[str, Any]],
                  block: bool) -> List[Any]:
        """Run a batch on the fallback rung: vmap-coalesced when the
        rung supports it, per-binding dispatch otherwise (interpreted
        rungs have no vmap batching rule but the answer is the same)."""
        if fb.engine_name in _BATCHABLE_ENGINES:
            return fb.batch(bindings, block=block)
        handles = [fb.submit(**b) for b in bindings]
        return [h.result() for h in handles] if block else handles

    def _batch_inner(self, bindings: List[Dict[str, Any]],
                     block: bool) -> List[Any]:
        if self.engine_name not in _BATCHABLE_ENGINES:
            raise TypeError(
                f"batched execution requires one of {_BATCHABLE_ENGINES} "
                f"(vmap over the whole-query program); engine "
                f"{self.engine_name!r} keeps per-binding dispatch")
        for b in bindings:
            self._check_bindings(b)
        if not self._param_specs:
            handle = self.submit()
            handles = [handle] * len(bindings)
            return [h.result() for h in handles] if block else handles
        bucket = ENG.batch_bucket(len(bindings))
        tracing = OT.TRACER.on
        mark = OT.TRACER.watermark() if tracing else 0
        with OT.span("execute", engine=self.engine_name, mode="batch",
                     bindings=len(bindings), bucket=bucket) as sp:
            exe = self._batch_executor(bucket)
            padded = bindings + [bindings[-1]] * (bucket - len(bindings))
            stacked = {
                s.name: np.asarray([ENG.require_param(b, s)
                                    for b in padded],
                                   T.numpy_dtype(s.dtype))
                for s in self._param_specs}
            t0 = time.perf_counter()
            out = exe.raw(self._catalog, self._device_cache, stacked)
            self.stats.run_s = time.perf_counter() - t0
        if tracing:
            sp.set(run_s=round(self.stats.run_s, 6))
            self._last_trace = OT.Trace(OT.TRACER.since(mark))
        handles = [AsyncResult(out, lambda o, i=i: exe.finalize_one(o, i))
                   for i in range(len(bindings))]
        return [h.result() for h in handles] if block else handles

    def _batch_executor(self, bucket: int) -> BatchExecutor:
        key = self.cache_key + (("batch", bucket),)
        cache = self._compile_cache
        exe = cache.lookup(key) if cache is not None else None
        if exe is None:
            with OT.span("compile", engine=self.engine_name,
                         kind="batch", bucket=bucket) as csp:
                store = self._store
                can_persist = False
                if store is not None:
                    can_persist, _ = _persistable(self.engine_name,
                                                  self._plan)
                if can_persist:
                    with OT.span("persist", op="load",
                                 bucket=bucket) as psp:
                        t0 = time.perf_counter()
                        exe, disposition = _load_persisted_exec(
                            store, _exec_digest(self.cache_key, bucket),
                            self._plan, self._catalog, self.engine_name,
                            self._param_specs, bucket=bucket)
                        psp.set(outcome=disposition
                                if exe is not None else "miss")
                    if exe is not None:
                        self.stats.compile_s += time.perf_counter() - t0
                        self.stats.disk_hit = True
                        if not self.stats.persist.startswith("hit"):
                            self.stats.persist = disposition
                        if cache is not None:
                            cache.insert(key, exe)
                        csp.set(cache="miss", disk="hit")
                        return exe
                t0 = time.perf_counter()
                exe = compile_batch_executor(self._plan, self._catalog,
                                             self._param_specs, bucket)
                self.stats.compile_s += time.perf_counter() - t0
                csp.set(cache="miss", disk="miss",
                        compile_s=round(time.perf_counter() - t0, 6))
                if cache is not None:
                    cache.insert(key, exe)
                if can_persist:
                    bschema = (None
                               if isinstance(self._plan, P.IterativeKernel)
                               else self._plan.schema(self._catalog))
                    with OT.span("persist", op="save", bucket=bucket):
                        _save_persisted_exec(
                            store, _exec_digest(self.cache_key, bucket),
                            exe, self.engine_name, self._param_specs,
                            bschema, bucket=bucket)
        return exe

    def count(self, **params: Any) -> int:
        return self.result(**params).num_rows()

    def scalar(self, name: Optional[str] = None, **params: Any):
        return self.result(**params).scalar(name)

    def __repr__(self):
        names = ", ".join(s.name for s in self._param_specs)
        return (f"Compiled<{self.engine_name}>({names})")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _add_index_decisions(p: P.Plan, catalog: P.Catalog,
                         report: Optional[Any], join_index: bool,
                         decisions: Optional[List] = None
                         ) -> Optional[Any]:
    """Record, per join, whether the build side probes the cached index
    or rebuilds in-program -- on the template's dispatch report (created
    when absent, so every compiled/parallel template with joins carries
    one even without ``native=True``)."""
    if not join_index:
        decisions = [(j, None, "join index cache disabled "
                      "(join_index=False)")
                     for j in _joins_of(p)]
    elif decisions is None:
        _, decisions = L.join_index_plan(p, catalog)
    if not decisions:
        return report
    from repro.native import registry as NR  # lazy: telemetry types only
    if report is None:
        report = NR.DispatchReport()
    for join, spec, reason in decisions:
        report.index_decisions.append(NR.Decision(
            pattern="join-index", node=join.describe(),
            fired=spec is not None,
            mode=("direct" if spec.direct else "sorted") if spec else "",
            reason="ok" if spec else reason))
    return report


def _joins_of(p: P.Plan) -> List[P.Plan]:
    out: List[P.Plan] = []

    def rec(n: P.Plan):
        if isinstance(n, P.Join):
            out.append(n)
        for c in n.children():
            rec(c)

    rec(p)
    return out


def lower_plan(p: P.Plan, catalog: P.Catalog, engine: str = "compiled",
               device_cache: Optional[ENG.DeviceCache] = None,
               compile_cache: Optional[CompileCache] = None,
               native: bool = False, mesh: Optional[Any] = None,
               axis: str = "data", join_index: bool = True,
               memory_budget: Optional[int] = None,
               morsel_rows: Optional[int] = None) -> Lowered:
    """Lower an (already optimized) plan for ``engine``.

    The DataFrame front end (``df.lower(engine=...)``) optimizes first
    and passes its context's device + compile caches; direct callers get
    process-wide defaults.

    ``join_index=False`` disables the build-side join index cache
    (DESIGN.md section 10): every join keeps its in-program argsort.
    This is the cold/baseline path benchmarks compare against; templates
    lowered with and without the cache get distinct cache keys.

    ``memory_budget`` (bytes) declares how much fast memory the spine
    stream may occupy: a plan whose bound-column working set exceeds it
    is rewritten for out-of-core morsel execution
    (:func:`repro.core.morsel.plan_morsels` -- the scan streams in
    fixed-size chunks through a ``fori_loop`` and partial aggregates
    merge with the parallel engine's recomposition rules).
    ``morsel_rows`` forces an explicit morsel size instead.  Both
    compose with ``native=True`` (kernels see morsel-sized streams) and
    with ``engine="parallel"`` (each shard streams its own morsels
    before the cross-shard merge); the morsel size is part of the
    template fingerprint.

    ``native=True`` (or ``engine="compiled-native"``, the registry
    alias) runs the :mod:`repro.native` dispatch pass over the plan
    first: fragments matched by the kernel-pattern registry lower onto
    Pallas kernels inside the same whole-query program, everything else
    keeps its jnp lowering, and the per-query
    :class:`repro.native.registry.DispatchReport` lands on
    ``Lowered.dispatch_report()`` / ``CompileStats.dispatch``.

    ``engine="parallel"`` runs the :mod:`repro.core.parallel` shard
    planner first: the plan is split into a row-partitioned parallel
    section and a merge/gather finish over ``mesh`` (default: a 1-D
    data mesh over every host device) along the named ``axis``.  The
    mesh shape is part of the template fingerprint -- one compiled SPMD
    program per mesh shape.  ``native=True`` composes: each shard
    dispatches its fragment onto the Pallas kernels, and the per-shard
    report lands on ``Lowered.dispatch_report()``.
    """
    dispatch_report = None
    # degradation-ladder re-lower source: the pre-rewrite plan and the
    # caller's lowering knobs, captured before shard/morsel/native
    # rewrites mutate the plan (repro.resilience.degrade re-lowers from
    # here on a weaker rung)
    degrade_src = dict(plan=p, catalog=catalog, engine=engine,
                       device_cache=device_cache,
                       compile_cache=compile_cache, native=native,
                       axis=axis, join_index=join_index,
                       memory_budget=memory_budget,
                       morsel_rows=morsel_rows)
    out_of_core = memory_budget is not None or morsel_rows is not None
    if engine == "parallel":
        # lazy import: registers the parallel engine; the shard planner
        # handles native annotation itself (partial aggregates first)
        # and the morsel wrap (per-shard partials stream their morsels)
        from repro.core import parallel as PAR
        with OT.span("shard_plan", axis=axis, native=native):
            p, dispatch_report = PAR.shard_plan(p, catalog, mesh=mesh,
                                                axis=axis, native=native,
                                                join_index=join_index,
                                                memory_budget=memory_budget,
                                                morsel_rows=morsel_rows)
    else:
        if mesh is not None:
            raise ValueError(
                f"mesh= applies to the 'parallel' engine, got {engine!r}")
        if native and engine == "compiled":
            engine = "compiled-native"
        if out_of_core:
            if engine not in ("compiled", "compiled-native"):
                raise ValueError(
                    "memory_budget/morsel_rows apply to the compiled, "
                    f"compiled-native and parallel engines, got {engine!r}")
            # morsel wrap BEFORE native annotation: the dispatch pass
            # must see (and kernel-annotate) the partial aggregate the
            # loop body actually computes
            from repro.core import morsel as MO
            with OT.span("morsel_plan", budget=memory_budget or 0,
                         morsel_rows=morsel_rows or 0):
                p = MO.plan_morsels(p, catalog,
                                    memory_budget=memory_budget,
                                    morsel_rows=morsel_rows)
        if engine == "compiled-native":
            # lazy import: registers the patterns + the engine alias
            from repro.native import dispatch as ND
            p, dispatch_report = ND.rewrite_plan(p, catalog,
                                                 join_index=join_index)
        elif native:
            raise ValueError(
                f"native=True requires the 'compiled' or 'parallel' "
                f"engine, got {engine!r}")
    index_specs: Optional[Dict[int, Any]] = None
    if engine in ("compiled", "compiled-native", "parallel"):
        if join_index:
            # resolved ONCE here; template_key and the report consume
            # it (build_callable re-resolves lazily at artifact time)
            with OT.span("index_plan"):
                index_specs, index_decisions = L.join_index_plan(
                    p, catalog)
        else:
            index_specs, index_decisions = {}, None
            if _joins_of(p):
                # disable on a PRIVATE root copy: the marker must not
                # leak onto a plan object the caller may re-lower with
                # the cache enabled
                p = p.with_children(p.children())
                p._join_index_disabled = True
        dispatch_report = _add_index_decisions(p, catalog, dispatch_report,
                                               join_index,
                                               decisions=index_decisions)
    eng = get_engine(engine)
    specs = P.params_of(p)
    key = template_key(engine, p, catalog, index_specs=index_specs)
    lowered = Lowered(p, catalog, eng, specs, key,
                      device_cache if device_cache is not None
                      else ENG._DEFAULT_CACHE,
                      compile_cache if compile_cache is not None
                      else _DEFAULT_COMPILE_CACHE,
                      dispatch_report=dispatch_report)
    lowered._degrade_src = degrade_src
    return lowered
