"""The three execution engines (DESIGN.md section 2).

``volcano``  -- operator-at-a-time numpy interpreter.  The Postgres-analogue
               baseline of the paper's Fig. 9 and the correctness oracle for
               everything else: it materialises exact-size compacted arrays
               after every operator.
``stage``    -- stage-granular compilation (Spark/Tungsten + Flare Level 1
               analogue): operator pipelines (scan/filter/project) fuse into
               their parent pipeline-breaker (join/aggregate/sort), each
               stage is jit-compiled separately, and stage outputs round-trip
               through the host -- the "communication through Spark's runtime
               system" overhead the paper measures in Fig. 5/6.
``compiled`` -- whole-query compilation (Flare Level 2): ONE XLA program for
               the entire plan; nothing materialises between operators.  The
               whole-query pipeline itself (AOT lower -> compile -> execute)
               lives in ``repro.core.stages``; this module's :func:`execute`
               front door delegates to it.

Two more engines register behind the same stages API: ``tuple`` (the
row-at-a-time Volcano baseline, ``repro.core.tuple_engine``) and
``parallel`` (the mesh-sharded whole-query engine, paper section 4.3 --
``repro.core.parallel``).

All five return a :class:`repro.core.lower.Result` with identical row
semantics, so the engines can be differentially tested against each other
(tests/test_system.py, tests/test_stages.py, and the hypothesis property
tests in tests/test_property.py).  The explicit ``Query -> Lowered ->
Compiled`` staging API over these engines is described in DESIGN.md
section 4.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import expr as E
from repro.core import lower as L
from repro.core import plan as P
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.persist import store as PS
from repro.relational import table as T
from repro.resilience import faults as FZ

# Pipeline breakers.  MapBatches breaks on the STAGE engine by design:
# Spark treats UDFs as black boxes and materialises around them (paper
# section 5.1) -- the fused whole-query engine is what removes that
# boundary (Flare Level 3).
_BREAKERS = (P.Join, P.Aggregate, P.Sort, P.Limit, P.MapBatches)


# ---------------------------------------------------------------------------
# process-wide cache telemetry (one aggregate view over every live cache)
# ---------------------------------------------------------------------------


def register_cache(cache: Any) -> Any:
    """Track ``cache`` in the process-wide telemetry registry.  The
    cache's class must define a ``kind`` attribute ("compile", "index",
    "device", ...) and ``__len__``; hit/miss counters are optional.
    Shim over :data:`repro.obs.metrics.REGISTRY` ("cache" domain)."""
    return OM.REGISTRY.register("cache", cache)


def cache_stats() -> Dict[str, Dict[str, Any]]:
    """One aggregate snapshot over every live cache in the process.

    Shim over :func:`repro.obs.metrics.snapshot` -- this is exactly its
    ``"caches"`` section, kept as the historical accessor.  Schema
    (stable, DESIGN.md section 12): per cache ``kind`` -- ``compile``
    (query templates), ``index`` (build-side join indexes), ``device``
    (resident columns) -- the keys are ``caches``, ``entries``,
    ``hits``, ``misses``, ``hit_rate``; ``compile`` and ``index``
    additionally carry a nested ``disk`` dict (the summed per-tier
    :class:`repro.persist.TierStats` across every live
    :class:`repro.persist.ArtifactStore`, zeros when none) so callers
    can attribute a memory-tier miss that was actually served from
    disk.  The full process view (dispatch counters, serve latencies,
    tracer state) is ``repro.obs.snapshot()``.
    """
    return OM.cache_section()


# ---------------------------------------------------------------------------
# batch-bucket policy for vmap-coalesced prepared-query execution
# ---------------------------------------------------------------------------


def batch_bucket(n: int) -> int:
    """The compile bucket serving a batch of ``n`` parameter bindings.

    Batched executables are shape-specialised on the binding-stack
    length, so compiling one per observed batch size would turn a busy
    server's ragged queues into a compile storm.  Buckets are the
    powers of two: a batch of ``n`` runs on the next-power-of-two
    executable with the tail padded by repeating the last binding
    (padding results are discarded).  The bucket is part of the
    CompileCache key (``repro.core.stages.Compiled.batch``), giving
    exactly ONE compile per (template, bucket) for the server's whole
    lifetime.
    """
    if n < 1:
        raise ValueError(f"batch of {n} bindings")
    return 1 << (n - 1).bit_length()


# ---------------------------------------------------------------------------
# device column cache ("persist" / preload semantics)
# ---------------------------------------------------------------------------


class UnindexableKeyError(ValueError):
    """Key column(s) cannot back a cached join index (values outside
    the engine's int32 key range).  ``preload`` skips such columns;
    joins over them keep their in-program lowering."""


@dataclasses.dataclass
class JoinIndex:
    """A build-side join index: the sorted permutation + sorted combined
    keys of a base table's key columns -- the device-resident "hash
    table" of the sorted-array join (DESIGN.md section 10).  Built ONCE
    per (table, key columns) at preload/first use and closed over by
    every compiled program that probes this build side; the in-program
    ``argsort`` the join would otherwise re-run per execution is gone.

    ``slots`` holds the slot tables of direct joins, one per combined
    key domain size (:meth:`slot_table`): derived from ``perm``/``keys``
    on first use, never persisted, and dropped with the index.
    """

    perm: jnp.ndarray     # int32 [n]: stable argsort of the combined keys
    keys: jnp.ndarray     # int32 [n]: combined keys, sorted
    unique: bool          # verified at build: no duplicate combined keys
    slots: Dict[int, jnp.ndarray] = dataclasses.field(
        default_factory=dict, repr=False)

    def slot_table(self, domain: int) -> jnp.ndarray:
        """int32 ``[domain]``: ``slots[k]`` is the build row the sorted
        probe finds for combined key ``k`` (the first in stable sort
        order, so the lowest row index among duplicates), -1 where no
        row holds ``k``.  Built on the device on first use per domain
        size; a key outside ``[0, domain)`` contradicts the declared
        domain and raises."""
        table = self.slots.get(domain)
        if table is None:
            with OT.span("slot_build", rows=int(self.keys.shape[0]),
                         domain=domain):
                lo, hi = (int(v) for v in
                          jax.device_get((self.keys[0], self.keys[-1])))
                if lo < 0 or hi >= domain:
                    raise ValueError(
                        f"join keys span [{lo}, {hi}], outside their "
                        f"declared domain [0, {domain}) (Field.domain)")
                table = _slot_table(self.perm, self.keys, domain)
            self.slots[domain] = table
        return table


@functools.partial(jax.jit, static_argnums=2)
def _slot_table(perm: jnp.ndarray, keys: jnp.ndarray,
                domain: int) -> jnp.ndarray:
    # scatter-min: duplicates resolve to the lowest row, whatever order
    # the scatter applies them in (perm < n < INT32_MAX)
    none = jnp.iinfo(jnp.int32).max
    first = jnp.full((domain,), none, jnp.int32).at[keys].min(perm)
    return jnp.where(first == none, jnp.int32(-1), first)


class IndexCache:
    """Caches :class:`JoinIndex` entries per (table object, key columns).

    The Flare lesson (paper section 4, Fig. 6) is that the join hash
    table belongs to the *data*, not the query: indexing happens at load
    time, execution only probes.  ``hits``/``misses`` give the same
    telemetry surface as :class:`repro.core.stages.CompileCache`.

    Declared-unique key columns (:attr:`repro.relational.table.Field.
    unique`) are *verified* against the data here: a false declaration
    fails loudly instead of silently mis-validating filtered build
    sides.

    ``store`` (or, when None, the ambient ``$FLARE_CACHE_DIR`` store)
    is the disk tier: a memory miss first tries
    ``<store>/v1/index/<digest>.flare`` -- the digest covers the raw
    key-column bytes, so changed data can never hit a stale index --
    and a fresh build writes through.  ``disk_hits`` counts builds this
    cache skipped by deserializing.
    """

    kind = "index"

    def __init__(self, store: Optional["PS.ArtifactStore"] = None):
        self._entries: Dict[Tuple, JoinIndex] = {}
        self.hits = 0
        self.misses = 0
        self.disk_hits = 0
        self.store = store
        register_cache(self)

    def _store(self) -> Optional["PS.ArtifactStore"]:
        return self.store if self.store is not None else PS.default_store()

    @staticmethod
    def _key(tbl: T.Table, key_cols: Tuple[str, ...],
             doms: Tuple[int, ...]) -> Tuple:
        # single-column keys combine to the raw column values, so the
        # domain bounds are not part of the index identity there
        return (id(tbl), tuple(key_cols),
                tuple(doms) if len(key_cols) > 1 else ())

    def get(self, tbl: T.Table, key_cols: Tuple[str, ...],
            doms: Tuple[int, ...] = ()) -> JoinIndex:
        key = self._key(tbl, key_cols, doms)
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            with OT.span("index_lookup", keys=",".join(key_cols),
                         rows=tbl.num_rows) as sp:
                store = self._store()
                digest = (PS.index_digest(tbl, tuple(key_cols),
                                          tuple(doms))
                          if store is not None else None)
                if store is not None:
                    entry = self._load_persisted(store, digest, tbl,
                                                 tuple(key_cols))
                    if entry is not None:
                        self.disk_hits += 1
                        sp.set(outcome="disk_hit")
                if entry is None:
                    with OT.span("index_build", keys=",".join(key_cols),
                                 rows=tbl.num_rows):
                        FZ.fault_point("index.build",
                                       keys=",".join(key_cols))
                        entry = self._build(tbl, tuple(key_cols),
                                            tuple(doms))
                    sp.set(outcome="built")
                    if store is not None:
                        self._save_persisted(store, digest, entry)
                self._entries[key] = entry
        else:
            self.hits += 1
            with OT.span("index_lookup", keys=",".join(key_cols),
                         outcome="hit"):
                pass
        return entry

    @staticmethod
    def _load_persisted(store: "PS.ArtifactStore", digest: str,
                        tbl: T.Table, key_cols: Tuple[str, ...]
                        ) -> Optional[JoinIndex]:
        loaded = store.load("index", digest)
        if loaded is None:
            return None
        header, sections = loaded
        meta = header.get("meta", {})
        try:
            n = int(meta["n"])
            unique = bool(meta["unique"])
            if len(sections) != 2:
                raise ValueError("expected perm + keys sections")
            perm = np.frombuffer(sections[0], np.int32)
            keys = np.frombuffer(sections[1], np.int32)
            if len(perm) != n or len(keys) != n or n != tbl.num_rows:
                raise ValueError("length mismatch")
        except (KeyError, TypeError, ValueError):
            store.demote_hit("index", "corrupt")
            return None
        # the declared-unique contract is verified against the data at
        # build time; the digest pins the data, so replaying the saved
        # verdict keeps a false declaration failing loudly here too
        declared = any(tbl.schema[c].unique for c in key_cols)
        if declared and not unique:
            raise ValueError(
                f"column(s) {list(key_cols)} are declared unique "
                f"(Field.unique) but hold duplicate keys")
        return JoinIndex(jnp.asarray(perm), jnp.asarray(keys), unique)

    @staticmethod
    def _save_persisted(store: "PS.ArtifactStore", digest: str,
                        entry: JoinIndex) -> None:
        perm = np.asarray(entry.perm, np.int32)
        keys = np.asarray(entry.keys, np.int32)
        store.save("index", digest,
                   {"n": int(len(perm)), "unique": bool(entry.unique)},
                   [perm.tobytes(), keys.tobytes()])

    @staticmethod
    def _build(tbl: T.Table, key_cols: Tuple[str, ...],
               doms: Tuple[int, ...]) -> JoinIndex:
        # combine in int64 first: casting to the engine's int32 keys
        # must be exact, and the uniqueness check must see the TRUE
        # values (an int64 PK that truncates into collisions is
        # unindexable, not a false "duplicate keys" declaration error)
        kb = np.asarray(tbl[key_cols[0]]).astype(np.int64)
        for c, d in zip(key_cols[1:], doms[1:]):
            kb = kb * np.int64(d) + np.asarray(tbl[c]).astype(np.int64)
        if len(kb) and (kb.min() < -(2 ** 31) or kb.max() >= 2 ** 31):
            raise UnindexableKeyError(
                f"combined join key over {list(key_cols)} exceeds the "
                f"engine's int32 key range")
        kb = kb.astype(np.int32)
        # stable, matching jnp.argsort/np "stable": cached-index and
        # in-program probes resolve duplicate keys to the SAME row
        perm = np.argsort(kb, kind="stable")
        keys = kb[perm]
        unique = bool(np.all(keys[1:] != keys[:-1])) if len(keys) else True
        declared = any(tbl.schema[c].unique for c in key_cols)
        if declared and not unique:
            raise ValueError(
                f"column(s) {list(key_cols)} are declared unique "
                f"(Field.unique) but hold duplicate keys")
        return JoinIndex(jnp.asarray(perm.astype(np.int32)),
                         jnp.asarray(keys), unique)

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def clear(self) -> None:
        self._entries.clear()
        self.hits = 0
        self.misses = 0


class DeviceCache:
    """Caches device-resident columns per (table object, column name).

    The paper's experiments distinguish "direct CSV" from "preloaded"
    execution; with a warm cache our engines run purely in-memory.
    ``indexes`` is the companion :class:`IndexCache` holding build-side
    join indexes (sorted permutation + sorted keys, and the slot tables
    of direct joins) with the same lifetime as the cached columns.
    """

    kind = "device"

    def __init__(self, store: Optional["PS.ArtifactStore"] = None):
        # (id(table), column) or (id(table), column, pad_to) -> device array
        self._cache: Dict[Tuple, jnp.ndarray] = {}
        self.indexes = IndexCache(store=store)
        register_cache(self)

    def __len__(self) -> int:
        return len(self._cache)

    def get(self, tbl: T.Table, name: str) -> jnp.ndarray:
        key = (id(tbl), name)
        arr = self._cache.get(key)
        if arr is None:
            arr = jnp.asarray(tbl[name])
            self._cache[key] = arr
        return arr

    def get_placed(self, tbl: T.Table, name: str, sharding: Any,
                   pad_to: Optional[int] = None) -> jax.Array:
        """Column placed with ``sharding`` once, zero-padded to
        ``pad_to`` rows, cached per (table, column, pad, sharding) --
        the sharding names its mesh, so each mesh keeps its own copy.
        The sharded ``parallel`` engine row-partitions its spine table
        across the mesh (``P(axis)``, padded to a multiple of the shard
        count; padding rows are masked off inside the program) and
        replicates the other tables (``P()``): placing them here once
        keeps every execution from scattering them from one device."""
        key = (id(tbl), name, pad_to, sharding)
        arr = self._cache.get(key)
        if arr is None:
            host = np.asarray(tbl[name])
            if pad_to is not None:
                if pad_to < tbl.num_rows:
                    raise ValueError(f"pad_to {pad_to} < table rows "
                                     f"{tbl.num_rows}")
                host = np.pad(host, (0, pad_to - tbl.num_rows))
            arr = jax.device_put(host, sharding)
            self._cache[key] = arr
        return arr

    def place(self, arr: jax.Array, sharding: Any) -> jax.Array:
        """A device array (a cached join index) placed with
        ``sharding`` once.  The entry keeps ``arr`` alive, so its id
        cannot be reused while the entry exists."""
        key = ("placed", id(arr), sharding)
        hit = self._cache.get(key)
        if hit is None or hit[0] is not arr:
            hit = (arr, jax.device_put(arr, sharding))
            self._cache[key] = hit
        return hit[1]

    def placements(self) -> List[Tuple[str, Optional[int], Any]]:
        """(column, pad_to, sharding) of every column placed by
        :meth:`get_placed`."""
        return [(k[1], k[2], k[3]) for k in self._cache if len(k) == 4]

    def get_index(self, tbl: T.Table, key_cols: Tuple[str, ...],
                  doms: Tuple[int, ...] = ()) -> JoinIndex:
        """The build-side join index for ``key_cols`` of ``tbl``
        (built lazily on first use, cached device-resident)."""
        return self.indexes.get(tbl, key_cols, doms)

    def clear(self):
        self._cache.clear()
        self.indexes.clear()


# ---------------------------------------------------------------------------
# compile telemetry
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CompileStats:
    """Telemetry for one lower/compile/execute pipeline.

    ``lower_s`` covers plan -> traced program (jaxpr), ``compile_s`` the
    XLA compile of that program; ``trace_compile_s`` is their sum, kept as
    a field for backward compatibility with pre-stages callers.
    ``cache_hit`` is True when :class:`repro.core.stages.CompileCache`
    already held the compiled executable for this template.
    ``dispatch`` carries the per-query native-kernel dispatch report
    (:class:`repro.native.registry.DispatchReport`) when the template
    was lowered with ``native=True`` / the ``compiled-native`` engine:
    which kernel patterns fired, which fragments fell back, and why.

    ``disk_hit`` is True when the executable came off the persistent
    store tier (no trace, no XLA compile of the plan); ``persist`` is
    the human-readable disposition of the disk tier for this compile
    ("hit:native", "hit:portable", "written", "unsupported: ...",
    "" when no store was in play).

    ``degraded`` is the degradation-ladder provenance: one dict per
    recorded hop (:class:`repro.resilience.degrade.DegradeEvent`) when
    a recoverable failure re-lowered this template on a weaker rung --
    empty on the happy path.  A degraded answer is correct but slower;
    consumers that care (benchmarks, the chaos gate) check this field.
    """

    trace_compile_s: float = 0.0
    cache_hit: bool = False
    lower_s: float = 0.0
    compile_s: float = 0.0
    run_s: float = 0.0
    engine: str = ""
    cache_key: Optional[Tuple] = None
    dispatch: Optional[Any] = None
    disk_hit: bool = False
    persist: str = ""
    degraded: Tuple[Dict[str, Any], ...] = ()


def require_param(params: Optional[Dict[str, Any]], spec: E.Param):
    """Fetch ``spec``'s binding or raise a clear prepared-query error."""
    if params is None or spec.name not in params:
        raise KeyError(
            f"unbound query parameter {spec.name!r} ({spec.dtype}); "
            f"bound: {sorted(params) if params else []}")
    return params[spec.name]


def scan_tables(p: P.Plan) -> List[str]:
    """Names of all tables scanned by ``p`` (with duplicates)."""
    out = []

    def rec(n):
        if isinstance(n, P.Scan):
            out.append(n.table)
        for c in n.children():
            rec(c)

    rec(p)
    return out


def scan_map(p: P.Plan) -> Dict[int, str]:
    """id(Scan node) -> table name, for argument binding."""
    out = {}

    def rec(n):
        if isinstance(n, P.Scan):
            out[id(n)] = n.table
        for c in n.children():
            rec(c)

    rec(p)
    return out


# ---------------------------------------------------------------------------
# stage-granular engine (Spark/Tungsten analogue)
# ---------------------------------------------------------------------------


class StageEngine:
    """Pipelines fuse into their parent breaker; each breaker is a stage.

    Stage outputs are materialised to the host between stages, modelling
    Spark's exchange/iterator boundaries (paper section 3.1: 80% of Q6 time
    was spent in exactly this glue).
    """

    def __init__(self):
        self._cache: Dict[Any, Tuple[Callable, List]] = {}
        self.stages_run = 0

    def execute(self, p: P.Plan, catalog: P.Catalog, cache: DeviceCache,
                params: Optional[Dict[str, Any]] = None):
        self.stages_run = 0
        self._param_env = {
            s.name: jnp.asarray(require_param(params, s), L._JNP_OF[s.dtype])
            for s in P.params_of(p)}
        if isinstance(p, P.IterativeKernel):
            # heterogeneous pipeline, Spark-style: the relational half
            # materialises through the host, then the training kernel
            # runs as its OWN jitted stage -- the staged baseline the
            # fused whole-query engine is measured against.
            cols, mask, info = self._run_stage(p.child, catalog, cache)
            return self._run_kernel_stage(p, cols, mask, info)
        cols, mask, info = self._run_stage(p, catalog, cache)
        schema = p.schema(catalog)
        dicts = {n: sc.dictionary for n, sc in info.cols.items()}
        cols = {n: cols[n] for n in schema.names}
        return L.Result(cols, mask, schema, dicts)

    def _run_kernel_stage(self, p: "P.IterativeKernel",
                          cols: Dict[str, np.ndarray],
                          mask: Optional[np.ndarray],
                          info: L.StaticInfo) -> L.ValueResult:
        self.stages_run += 1
        names = list(p.required_columns())
        n = info.n_rows
        specs = tuple({v.name: v for _, v in p.hyper
                       if isinstance(v, E.Param)}.values())

        def fn(*flat):
            it = iter(flat)
            kcols = {m: next(it) for m in names}
            kmask = next(it)
            env = {s.name: next(it) for s in specs}
            stream = L.Stream(kcols, kmask,
                              L.StaticInfo({m: info.cols[m] for m in names},
                                           n))
            return L.apply_kernel(p, stream, env or None)

        key = ("kernel", p.fingerprint(), n)
        jfn = self._cache.get(key)
        if jfn is None:
            jfn = jax.jit(fn)
            self._cache[key] = jfn
        args = [jnp.asarray(cols[m]) for m in names]
        args.append(jnp.asarray(mask if mask is not None
                                else np.ones(n, np.bool_)))
        args.extend(self._param_env[s.name] for s in specs)
        out = jfn(*args)
        return L.ValueResult(jax.tree_util.tree_map(np.asarray, out))

    def _run_stage(self, root: P.Plan, catalog: P.Catalog,
                   cache: DeviceCache):
        """Execute the stage rooted at ``root``; returns host arrays."""
        self.stages_run += 1
        leaves: Dict[int, Tuple[Dict[str, np.ndarray], Optional[np.ndarray],
                                L.StaticInfo]] = {}

        def gather(n: P.Plan, is_root: bool):
            if isinstance(n, P.Scan):
                leaves[id(n)] = ("scan", n)
                return
            if isinstance(n, _BREAKERS) and not is_root:
                leaves[id(n)] = ("mat", self._run_stage(n, catalog, cache))
                return
            for c in n.children():
                gather(c, False)

        gather(root, True)

        needed = L.required_scan_columns(root, catalog)
        leaf_ids = sorted(leaves)
        # flat argument layout: per leaf, its columns then its mask (mat only)
        layout: List[Tuple[int, List[str], bool]] = []
        args: List[np.ndarray] = []
        infos: Dict[int, L.StaticInfo] = {}
        for lid in leaf_ids:
            kind, payload = leaves[lid]
            if kind == "scan":
                scan = payload
                tbl = catalog.table(scan.table)
                names = needed.get(lid) or tbl.schema.names[:1]
                layout.append((lid, names, False))
                infos[lid] = L.StaticInfo(
                    {n: L._static_of_scan(tbl).cols[n] for n in names},
                    tbl.num_rows)
                for n in names:
                    args.append(cache.get(tbl, n))
            else:
                mcols, mmask, minfo = payload
                names = list(mcols)
                layout.append((lid, names, True))
                infos[lid] = minfo
                for n in names:
                    args.append(jnp.asarray(mcols[n]))
                args.append(jnp.asarray(
                    mmask if mmask is not None
                    else np.ones(minfo.n_rows, np.bool_)))

        # trailing args: one scalar per Param placeholder of this stage's
        # subtree, traced so one jitted stage serves every binding
        # (prepared-statement reuse); the spec list is a function of
        # root.fingerprint(), keeping the jit-cache key consistent
        specs = P.params_of(root)
        args.extend(self._param_env[s.name] for s in specs)

        def fn(*flat):
            it = iter(flat)
            scans: Dict[int, L.Stream] = {}
            for lid, names, has_mask in layout:
                cols = {n: next(it) for n in names}
                mask = next(it) if has_mask else None
                scans[lid] = L.Stream(cols, mask, infos[lid])
            env = {s.name: next(it) for s in specs}
            stream = L.lower_node(root, catalog, scans, env or None)
            return stream.cols, stream.the_mask()

        key = (root.fingerprint(),
               tuple((lid, tuple(names), has_mask, infos[lid].n_rows,
                      tuple(hash(infos[lid].cols[n].dictionary or ())
                            for n in names))
                     for lid, names, has_mask in layout))
        jfn = self._cache.get(key)
        if jfn is None:
            jfn = jax.jit(fn)
            self._cache[key] = jfn
        out_cols, mask = jfn(*args)
        # host round-trip = the runtime-boundary overhead being modelled
        out_cols = {k: np.asarray(v) for k, v in out_cols.items()}
        return out_cols, np.asarray(mask), L.static_info(root, catalog)


# ---------------------------------------------------------------------------
# volcano engine (numpy oracle)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _VStream:
    cols: Dict[str, np.ndarray]
    dicts: Dict[str, Optional[Tuple[str, ...]]]
    domains: Dict[str, Optional[int]]


class VolcanoEngine:
    """Operator-at-a-time interpreter over compacted numpy arrays.

    Semantics deliberately mirror the compiled engine (left-join zero fill,
    group-code output order, N:1 joins) so results are comparable
    element-for-element.  Arithmetic runs in float64: this is the
    high-precision oracle.
    """

    def execute(self, p: P.Plan, catalog: P.Catalog,
                cache: DeviceCache = None,
                params: Optional[Dict[str, Any]] = None):
        self._params = {
            s.name: np.asarray(require_param(params, s),
                               T.numpy_dtype(s.dtype))[()]
            for s in P.params_of(p)}
        if isinstance(p, P.IterativeKernel):
            return self._train(p, catalog)
        vs = self._run(p, catalog)
        schema = p.schema(catalog)
        cols = {n: vs.cols[n] for n in schema.names}
        return L.Result(cols, None, schema,
                        {n: vs.dicts.get(n) for n in schema.names})

    def _train(self, p: "P.IterativeKernel",
               catalog: P.Catalog) -> L.ValueResult:
        """Interpreted heterogeneous fallback: child rows are compacted
        exact-size, so the kernel sees all-ones weights -- numerically
        the same math as the fused engine's masked padded batch."""
        vs = self._run(p.child, catalog)
        n = len(next(iter(vs.cols.values())))
        x = (np.stack([vs.cols[c].astype(np.float32) for c in p.features],
                      axis=1) if n else
             np.zeros((0, len(p.features)), np.float32))
        y = (vs.cols[p.label].astype(np.float32)
             if p.label is not None else None)
        w = np.ones((n,), np.float32)
        hyper = {}
        for k, v in p.hyper:
            hyper[k] = (self._params[v.name] if isinstance(v, E.Param)
                        else v)
        out = p.kernel(x, y, weights=w, **hyper)
        return L.ValueResult(jax.tree_util.tree_map(np.asarray, out))

    # -- operators -----------------------------------------------------------

    def _run(self, p: P.Plan, catalog: P.Catalog) -> _VStream:
        if isinstance(p, P.Scan):
            tbl = catalog.table(p.table)
            return _VStream(
                {f.name: tbl[f.name] for f in tbl.schema},
                {f.name: tbl.dictionary(f.name) for f in tbl.schema},
                {f.name: f.domain for f in tbl.schema})
        if isinstance(p, P.Filter):
            c = self._run(p.child, catalog)
            m = np.asarray(self._eval(p.pred, c), dtype=bool)
            return _VStream({n: v[m] for n, v in c.cols.items()},
                            c.dicts, c.domains)
        if isinstance(p, P.Project):
            c = self._run(p.child, catalog)
            cols, dicts, doms = {}, {}, {}
            for name, e in p.outputs:
                cols[name] = np.asarray(self._eval(e, c))
                dicts[name] = c.dicts.get(e.name) if isinstance(e, E.Col) else None
                if isinstance(e, E.Col):
                    doms[name] = c.domains.get(e.name)
                elif isinstance(e, E.WithDomain):
                    doms[name] = e.domain
                    if isinstance(e.arg, E.Col):
                        dicts[name] = c.dicts.get(e.arg.name)
                else:
                    doms[name] = None
            return _VStream(cols, dicts, doms)
        if isinstance(p, P.MapBatches):
            c = self._run(p.child, catalog)
            outs = p.fn({k: np.asarray(c.cols[k]) for k in p.columns})
            if set(outs) != set(p.out_names):
                raise TypeError(
                    f"map_batches {p.name!r} returned {sorted(outs)}, "
                    f"declared {sorted(p.out_names)}")
            produced = set(p.out_names)
            n_in = len(next(iter(c.cols.values())))
            cols = {n: v for n, v in c.cols.items() if n not in produced}
            dicts = {n: d for n, d in c.dicts.items() if n not in produced}
            doms = {n: d for n, d in c.domains.items() if n not in produced}
            for f in p.out_fields:
                v = np.asarray(outs[f.name])
                if v.shape != (n_in,):
                    raise TypeError(
                        f"map_batches {p.name!r} output {f.name!r} has "
                        f"shape {v.shape}; expected ({n_in},) -- batch "
                        "UDFs must be length-preserving 1-D columns")
                cols[f.name] = v.astype(T.numpy_dtype(f.dtype))
                dicts[f.name] = None
                doms[f.name] = f.domain
            return _VStream(cols, dicts, doms)
        if isinstance(p, P.Join):
            return self._join(p, catalog)
        if isinstance(p, P.Aggregate):
            return self._aggregate(p, catalog)
        if isinstance(p, P.Sort):
            c = self._run(p.child, catalog)
            keys = []
            for name, asc in reversed(p.by):
                v = c.cols[name]
                if not asc:
                    v = -v.astype(np.float64) if v.dtype.kind in "fiu" else v
                keys.append(v)
            order = np.lexsort(tuple(keys)) if keys else np.arange(
                len(next(iter(c.cols.values()))))
            return _VStream({n: v[order] for n, v in c.cols.items()},
                            c.dicts, c.domains)
        if isinstance(p, P.Limit):
            c = self._run(p.child, catalog)
            return _VStream({n: v[: p.n] for n, v in c.cols.items()},
                            c.dicts, c.domains)
        raise TypeError(p)

    def _join(self, p: P.Join, catalog: P.Catalog) -> _VStream:
        left = self._run(p.left, catalog)
        right = self._run(p.right, catalog)
        doms = []
        for lk, rk in zip(p.left_on, p.right_on):
            dl = left.dicts.get(lk)
            gl = len(dl) if dl is not None else left.domains.get(lk)
            dr = right.dicts.get(rk)
            gr = len(dr) if dr is not None else right.domains.get(rk)
            doms.append(max(gl or 0, gr or 0) or (1 << 31))
        kp = self._combine([left.cols[k] for k in p.left_on], doms)
        kb = self._combine([right.cols[k] for k in p.right_on], doms)
        perm = np.argsort(kb, kind="stable")
        kb_s = kb[perm]
        idx = np.searchsorted(kb_s, kp)
        idx_c = np.clip(idx, 0, max(len(kb_s) - 1, 0))
        if len(kb_s):
            matched = kb_s[idx_c] == kp
        else:
            matched = np.zeros(len(kp), bool)
        if p.how == "semi":
            return _VStream({n: v[matched] for n, v in left.cols.items()},
                            left.dicts, left.domains)
        if p.how == "anti":
            keep = ~matched
            return _VStream({n: v[keep] for n, v in left.cols.items()},
                            left.dicts, left.domains)
        cols, dicts, domsout = dict(left.cols), dict(left.dicts), dict(left.domains)
        for name, v in right.cols.items():
            if name in p.right_on:
                continue
            g = v[perm][idx_c] if len(kb_s) else np.zeros(len(kp), v.dtype)
            if p.how == "left":
                g = np.where(matched, g, np.zeros((), v.dtype))
            cols[name] = g
            dicts[name] = right.dicts.get(name)
            domsout[name] = right.domains.get(name)
        if p.how == "inner":
            cols = {n: v[matched] for n, v in cols.items()}
        return _VStream(cols, dicts, domsout)

    @staticmethod
    def _combine(keys, doms):
        out = keys[0].astype(np.int64)
        for k, d in zip(keys[1:], doms[1:]):
            out = out * np.int64(d) + k.astype(np.int64)
        return out

    def _aggregate(self, p: P.Aggregate, catalog: P.Catalog) -> _VStream:
        c = self._run(p.child, catalog)
        n = len(next(iter(c.cols.values())))
        if not p.keys:
            cols = {}
            for a in p.aggs:
                raw = None if a.arg is None else np.asarray(
                    self._eval(a.arg, c))
                v = None if raw is None else raw.astype(np.float64)
                cols[a.name] = np.asarray(
                    [self._agg_all(a.op, v, n,
                                   raw.dtype if raw is not None
                                   else None)])
            return _VStream(cols, {k: None for k in cols},
                            {k: None for k in cols})
        doms = []
        for k in p.keys:
            d = c.dicts.get(k)
            doms.append(len(d) if d is not None else c.domains[k])
        strides = []
        acc = 1
        for d in reversed(doms):
            strides.append(acc)
            acc *= d
        strides.reverse()
        code = np.zeros(n, np.int64)
        for k, s in zip(p.keys, strides):
            code += c.cols[k].astype(np.int64) * s
        groups, inv = np.unique(code, return_inverse=True)  # sorted: matches compiled group-code order
        g = len(groups)
        cols, dicts, domsout = {}, {}, {}
        for k, s, d in zip(p.keys, strides, doms):
            cols[k] = ((groups // s) % d).astype(c.cols[k].dtype)
            dicts[k] = c.dicts.get(k)
            domsout[k] = c.domains.get(k)
        cnt = np.bincount(inv, minlength=g)
        for a in p.aggs:
            if a.op == "count":
                cols[a.name] = cnt.astype(np.int64)
                continue
            v = np.asarray(self._eval(a.arg, c))
            vf = v.astype(np.float64)
            if a.op == "sum":
                cols[a.name] = np.bincount(inv, weights=vf, minlength=g)
            elif a.op == "avg":
                s_ = np.bincount(inv, weights=vf, minlength=g)
                cols[a.name] = s_ / np.maximum(cnt, 1)
            elif a.op in ("min", "max", "any"):
                fill = np.inf if a.op == "min" else -np.inf
                out = np.full(g, fill)
                ufn = np.minimum if a.op == "min" else np.maximum
                ufn.at(out, inv, vf)
                cols[a.name] = out.astype(v.dtype) if a.op == "any" else out
            if a.op == "any" and isinstance(a.arg, E.Col):
                dicts[a.name] = c.dicts.get(a.arg.name)
                domsout[a.name] = c.domains.get(a.arg.name)
            else:
                dicts[a.name] = None
                domsout[a.name] = None
        return _VStream(cols, dicts, domsout)

    @staticmethod
    def _agg_all(op, v, n, dtype=None):
        # empty-input sentinels match the compiled engine's masked fills
        # (f32 finfo.max / int32 iinfo.max, NOT inf)
        def hi():
            return (float(np.finfo(np.float32).max)
                    if dtype is None or dtype.kind == "f"
                    else float(np.iinfo(np.int32).max))

        if op == "count":
            return np.int64(n)
        if op == "sum":
            return v.sum() if len(v) else 0.0
        if op == "avg":
            return v.mean() if len(v) else 0.0
        if op == "min":
            return v.min() if len(v) else hi()
        if op == "max":
            return v.max() if len(v) else -hi()
        raise ValueError(op)

    # -- expressions over numpy ------------------------------------------------

    def _eval(self, e: E.Expr, s: _VStream):
        if isinstance(e, E.Col):
            return s.cols[e.name]
        if isinstance(e, E.Lit):
            return e.value
        if isinstance(e, E.Param):
            return self._params[e.name]
        if isinstance(e, E.BinOp):
            l, r = self._eval(e.left, s), self._eval(e.right, s)
            if e.op == "/":
                return np.asarray(l, np.float64) / np.asarray(r, np.float64)
            return {"+": np.add, "-": np.subtract,
                    "*": np.multiply}[e.op](l, r)
        if isinstance(e, E.Cmp):
            ld = s.dicts.get(e.left.name) if isinstance(e.left, E.Col) else None
            rd = s.dicts.get(e.right.name) if isinstance(e.right, E.Col) else None
            if ld is not None and isinstance(e.right, E.Lit):
                return self._cmp_code(e.op, s.cols[e.left.name], ld,
                                      e.right.value)
            if rd is not None and isinstance(e.left, E.Lit):
                flipped = {"<": ">", ">": "<", "<=": ">=", ">=": "<=",
                           "==": "==", "!=": "!="}[e.op]
                return self._cmp_code(flipped, s.cols[e.right.name], rd,
                                      e.left.value)
            l, r = self._eval(e.left, s), self._eval(e.right, s)
            return {"<": np.less, "<=": np.less_equal, ">": np.greater,
                    ">=": np.greater_equal, "==": np.equal,
                    "!=": np.not_equal}[e.op](l, r)
        if isinstance(e, E.BoolOp):
            vals = [np.asarray(self._eval(a, s), bool) for a in e.args]
            out = vals[0]
            for v in vals[1:]:
                out = (out & v) if e.op == "and" else (out | v)
            return out
        if isinstance(e, E.Not):
            return ~np.asarray(self._eval(e.arg, s), bool)
        if isinstance(e, E.InSet):
            d = s.dicts.get(e.arg.name) if isinstance(e.arg, E.Col) else None
            v = self._eval(e.arg, s)
            if d is not None:
                codes = [d.index(x) for x in e.values if x in d]
                return np.isin(v, codes)
            return np.isin(v, e.values)
        if isinstance(e, E.StrPred):
            d = s.dicts[e.arg.name]
            lut = np.asarray([L._match_str(e.kind, x, e.params) for x in d],
                             bool)
            return lut[self._eval(e.arg, s)]
        if isinstance(e, E.IfThenElse):
            return np.where(np.asarray(self._eval(e.cond, s), bool),
                            self._eval(e.then, s), self._eval(e.other, s))
        if isinstance(e, E.Cast):
            return np.asarray(self._eval(e.arg, s)).astype(
                T.numpy_dtype(e.dtype))
        if isinstance(e, E.WithDomain):
            return self._eval(e.arg, s)
        if isinstance(e, E.Udf):
            args = [np.asarray(self._eval(a, s)) for a in e.args]
            return np.asarray(e.fn(*args))
        raise TypeError(e)

    @staticmethod
    def _cmp_code(op, codes, dictionary, value):
        try:
            code = dictionary.index(value)
        except ValueError:
            if op == "==":
                return np.zeros(codes.shape, bool)
            if op == "!=":
                return np.ones(codes.shape, bool)
            code = int(np.searchsorted(np.asarray(dictionary, object), value))
            if op in ("<", "<="):
                return codes < code
            return codes >= code
        return {"<": np.less, "<=": np.less_equal, ">": np.greater,
                ">=": np.greater_equal, "==": np.equal,
                "!=": np.not_equal}[op](codes, code)


# ---------------------------------------------------------------------------
# front door
# ---------------------------------------------------------------------------

_DEFAULT_CACHE = DeviceCache()


def execute(p: P.Plan, catalog: P.Catalog, engine: str = "compiled",
            cache: Optional[DeviceCache] = None,
            stats: Optional[CompileStats] = None,
            params: Optional[Dict[str, Any]] = None,
            compile_cache=None) -> L.Result:
    """One-shot execute: lower + compile + run through the stages API.

    Thin convenience over ``repro.core.stages.lower_plan`` -- prepared
    queries that run more than once should hold on to the
    :class:`repro.core.stages.Compiled` object instead.
    """
    from repro.core import stages  # late import: stages builds on engines

    cache = cache or _DEFAULT_CACHE
    lowered = stages.lower_plan(p, catalog, engine=engine,
                                device_cache=cache,
                                compile_cache=compile_cache)
    compiled = lowered.compile()
    out = compiled.result(**(params or {}))
    if stats is not None:
        s = compiled.stats
        (stats.trace_compile_s, stats.cache_hit, stats.lower_s,
         stats.compile_s, stats.run_s, stats.engine, stats.cache_key,
         stats.dispatch) = (
            s.trace_compile_s, s.cache_hit, s.lower_s, s.compile_s,
            s.run_s, s.engine, s.cache_key, s.dispatch)
    return out
