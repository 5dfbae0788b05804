"""Whole-query lowering: logical plan -> ONE traced JAX function.

This is the Flare Level 2 analogue (paper section 4): the *entire* optimized
plan is lowered into a single program, so that operator pipelines fuse and
nothing materialises between operators.  Where the paper emits C and
compiles with GCC, we trace into a jaxpr and compile with XLA.

TPU adaptation (DESIGN.md section 3)::

    Filter      -> boolean selection mask (predication, never compacts)
    Hash join   -> N:1 / PK-FK join against a build-side index made once:
                   a dense key domain (at most 8 slots per build row)
                   probes a slot table of row positions with one gather;
                   sparse or undeclared keys binary-search the sorted
                   keys (searchsorted + gather)
    Hash agg    -> segment-sum onto the dense, statically-bounded group
                   domain derived from dictionaries / key domains
    Strings     -> int32 dictionary codes; string predicates evaluated on
                   the tiny dictionary at *lowering* time and baked in as
                   lookup tables (Parquet-style dictionary filtering)

Lowering runs in two phases.  Phase A (host, before tracing) propagates
static information: dictionaries, key domains, join key-combination
constants.  Phase B is the traced function over device arrays.
"""
from __future__ import annotations

import dataclasses
import fnmatch
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import expr as E
from repro.core import ml as ML
from repro.core import plan as P
from repro.obs import export as OX
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.relational import table as T

_I32_MAX = np.int32(2 ** 31 - 1)

#: A cached join probes a slot table over its combined key domain when
#: the domain holds at most this many slots per build row (o_orderkey's
#: sparse keys take 4); a sparser domain keeps the binary search.
_DIRECT_SLOTS_PER_ROW = 8

# ---------------------------------------------------------------------------
# static (phase A) column info
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StaticCol:
    dtype: str
    dictionary: Optional[Tuple[str, ...]] = None
    domain: Optional[int] = None  # dense-int key domain (exclusive bound)

    @property
    def group_domain(self) -> Optional[int]:
        if self.dictionary is not None:
            return len(self.dictionary)
        return self.domain


@dataclasses.dataclass
class StaticInfo:
    """Phase-A result for one plan node's output stream."""

    cols: Dict[str, StaticCol]
    n_rows: int  # static row bound of the stream


def _static_of_scan(tbl: T.Table) -> StaticInfo:
    cols = {}
    for f in tbl.schema:
        cols[f.name] = StaticCol(f.dtype, tbl.dictionary(f.name), f.domain)
    return StaticInfo(cols, tbl.num_rows)


# ---------------------------------------------------------------------------
# stream: the traced value flowing between operators
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Stream:
    cols: Dict[str, jnp.ndarray]
    mask: Optional[jnp.ndarray]  # bool [n] or None (= all valid)
    info: StaticInfo

    @property
    def n(self) -> int:
        return self.info.n_rows

    def the_mask(self) -> jnp.ndarray:
        if self.mask is None:
            return jnp.ones((self.n,), dtype=jnp.bool_)
        return self.mask


# ---------------------------------------------------------------------------
# expression evaluation (phase B, traced)
# ---------------------------------------------------------------------------

_JNP_OF = {
    T.INT32: jnp.int32, T.INT64: jnp.int32,  # device int64 needs x64; int32 suffices at our scales (checked in phase A)
    T.FLOAT32: jnp.float32, T.FLOAT64: jnp.float32,
    T.BOOL: jnp.bool_, T.DATE: jnp.int32, T.STRING: jnp.int32,
}


def _dict_of(e: E.Expr, info: StaticInfo) -> Optional[Tuple[str, ...]]:
    if isinstance(e, E.Col):
        return info.cols[e.name].dictionary
    return None


def _str_code(dictionary: Tuple[str, ...], value: str) -> int:
    """Code of ``value`` in a sorted dictionary, or -1 if absent."""
    try:
        return dictionary.index(value)
    except ValueError:
        return -1


def eval_expr(e: E.Expr, stream: Stream,
              params: Optional[Dict[str, Any]] = None) -> jnp.ndarray:
    info = stream.info
    if isinstance(e, E.Col):
        return stream.cols[e.name]
    if isinstance(e, E.Lit):
        if isinstance(e.value, str):
            raise TypeError("string literal outside comparison")
        return jnp.asarray(e.value)
    if isinstance(e, E.Param):
        if params is None or e.name not in params:
            raise KeyError(
                f"unbound query parameter {e.name!r}; pass a binding, e.g. "
                f"lowered.compile()({e.name}=...)")
        return params[e.name]
    if isinstance(e, E.BinOp):
        l, r = eval_expr(e.left, stream, params), eval_expr(e.right, stream, params)
        if e.op == "+":
            return l + r
        if e.op == "-":
            return l - r
        if e.op == "*":
            return l * r
        if e.op == "/":
            num = l.astype(jnp.float32) if jnp.issubdtype(l.dtype, jnp.integer) else l
            den = r.astype(jnp.float32) if jnp.issubdtype(r.dtype, jnp.integer) else r
            return num / den
        raise ValueError(e.op)
    if isinstance(e, E.Cmp):
        # string comparison -> dictionary code comparison (codes are in
        # dictionary == lexical order, so <,> are order-preserving too).
        ldict = _dict_of(e.left, info)
        rdict = _dict_of(e.right, info)
        if ldict is not None and isinstance(e.right, E.Lit):
            code = _str_code(ldict, e.right.value)
            l = eval_expr(e.left, stream, params)
            return _cmp_with_code(e.op, l, code, ldict, e.right.value)
        if rdict is not None and isinstance(e.left, E.Lit):
            flipped = {"<": ">", ">": "<", "<=": ">=", ">=": "<=",
                       "==": "==", "!=": "!="}[e.op]
            code = _str_code(rdict, e.left.value)
            r = eval_expr(e.right, stream, params)
            return _cmp_with_code(flipped, r, code, rdict, e.left.value)
        if ldict is not None and rdict is not None:
            if ldict != rdict:
                raise TypeError("cross-dictionary string comparison "
                                "unsupported in compiled engine")
            return _apply_cmp(e.op, eval_expr(e.left, stream, params),
                              eval_expr(e.right, stream, params))
        return _apply_cmp(e.op, eval_expr(e.left, stream, params),
                          eval_expr(e.right, stream, params))
    if isinstance(e, E.BoolOp):
        vals = [eval_expr(a, stream, params) for a in e.args]
        out = vals[0]
        for v in vals[1:]:
            out = (out & v) if e.op == "and" else (out | v)
        return out
    if isinstance(e, E.Not):
        return ~eval_expr(e.arg, stream, params)
    if isinstance(e, E.InSet):
        d = _dict_of(e.arg, info)
        arg = eval_expr(e.arg, stream, params)
        if d is not None:
            codes = [c for c in (_str_code(d, v) for v in e.values) if c >= 0]
            if not codes:
                return jnp.zeros(arg.shape, jnp.bool_)
            out = arg == codes[0]
            for c in codes[1:]:
                out = out | (arg == c)
            return out
        out = arg == e.values[0]
        for v in e.values[1:]:
            out = out | (arg == v)
        return out
    if isinstance(e, E.StrPred):
        d = _dict_of(e.arg, info)
        if d is None:
            raise TypeError(f"{e.kind} on non-string column")
        lut = np.asarray([_match_str(e.kind, s, e.params) for s in d],
                         dtype=np.bool_)
        codes = eval_expr(e.arg, stream, params)
        return jnp.asarray(lut)[codes]
    if isinstance(e, E.IfThenElse):
        return jnp.where(eval_expr(e.cond, stream, params),
                         eval_expr(e.then, stream, params),
                         eval_expr(e.other, stream, params))
    if isinstance(e, E.Cast):
        return eval_expr(e.arg, stream, params).astype(_JNP_OF[e.dtype])
    if isinstance(e, E.WithDomain):
        return eval_expr(e.arg, stream, params)
    if isinstance(e, E.Udf):
        args = [eval_expr(a, stream, params) for a in e.args]
        return e.fn(*args)  # staged: traced straight into this program
    raise TypeError(f"cannot lower {e!r}")


def _cmp_with_code(op, codes, code, dictionary, value):
    if code < 0:
        # literal absent from dictionary: == is all-false, != all-true;
        # for ordering, fall back to position where it would be inserted.
        if op == "==":
            return jnp.zeros(codes.shape, jnp.bool_)
        if op == "!=":
            return jnp.ones(codes.shape, jnp.bool_)
        code = int(np.searchsorted(np.asarray(dictionary, dtype=object),
                                   value))
        if op in ("<", "<="):
            return codes < code
        return codes >= code
    return _apply_cmp(op, codes, jnp.int32(code))


def _apply_cmp(op, l, r):
    return {"<": jnp.less, "<=": jnp.less_equal, ">": jnp.greater,
            ">=": jnp.greater_equal, "==": jnp.equal,
            "!=": jnp.not_equal}[op](l, r)


def _match_str(kind: str, s: str, params: Tuple[str, ...]) -> bool:
    if kind == "startswith":
        return s.startswith(params[0])
    if kind == "endswith":
        return s.endswith(params[0])
    if kind == "contains":
        return params[0] in s
    if kind == "like":
        return fnmatch.fnmatchcase(s, params[0].replace("%", "*").replace("_", "?"))
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# phase A: static info propagation
# ---------------------------------------------------------------------------


def static_info(p: P.Plan, catalog: P.Catalog) -> StaticInfo:
    hook = getattr(p, "static_info_hook", None)
    if hook is not None:  # custom-lowering nodes (see lower_node)
        return hook(catalog)
    if isinstance(p, P.Scan):
        return _static_of_scan(catalog.table(p.table))
    if isinstance(p, P.Filter):
        return static_info(p.child, catalog)
    if isinstance(p, P.MapBatches):
        child = static_info(p.child, catalog)
        produced = set(p.out_names)
        cols = {n: sc for n, sc in child.cols.items() if n not in produced}
        for f in p.out_fields:
            cols[f.name] = StaticCol(f.dtype, None, f.domain)
        return StaticInfo(cols, child.n_rows)
    if isinstance(p, P.Project):
        child = static_info(p.child, catalog)
        schema = p.child.schema(catalog)
        cols = {}
        for name, e in p.outputs:
            if isinstance(e, E.Col):
                cols[name] = child.cols[e.name]
            elif isinstance(e, E.WithDomain):
                inner = (child.cols[e.arg.name] if isinstance(e.arg, E.Col)
                         else StaticCol(E.infer_dtype(e.arg, schema)))
                cols[name] = StaticCol(inner.dtype, inner.dictionary,
                                       e.domain)
            else:
                cols[name] = StaticCol(E.infer_dtype(e, schema))
        return StaticInfo(cols, child.n_rows)
    if isinstance(p, P.Join):
        left = static_info(p.left, catalog)
        right = static_info(p.right, catalog)
        if p.how in ("semi", "anti"):
            return left
        cols = dict(left.cols)
        for name, sc in right.cols.items():
            if name in p.right_on:
                continue
            cols[name] = sc
        return StaticInfo(cols, left.n_rows)
    if isinstance(p, P.Aggregate):
        child = static_info(p.child, catalog)
        strides, domain = _group_layout(p, child)
        cols = {}
        for k in p.keys:
            cols[k] = child.cols[k]
        schema = p.schema(catalog)
        for a in p.aggs:
            if a.op == "any" and isinstance(a.arg, E.Col):
                cols[a.name] = child.cols[a.arg.name]  # keeps dict/domain
            else:
                cols[a.name] = StaticCol(schema[a.name].dtype)
        n = domain if p.keys else 1
        return StaticInfo(cols, n)
    if isinstance(p, (P.Sort,)):
        return static_info(p.child, catalog)
    if isinstance(p, P.Limit):
        child = static_info(p.child, catalog)
        return StaticInfo(child.cols, min(child.n_rows, p.n))
    raise TypeError(f"no static info for {p!r}")


def _group_layout(p: P.Aggregate, child: StaticInfo) -> Tuple[List[int], int]:
    """Strides and total size of the dense group-code domain."""
    doms = []
    for k in p.keys:
        g = child.cols[k].group_domain
        if g is None:
            raise TypeError(
                f"aggregate key '{k}' needs a dictionary or a dense integer "
                f"domain (Field.domain) for TPU direct-indexed aggregation")
        doms.append(g)
    total = 1
    for d in doms:
        total *= d
    if total > (1 << 26):
        raise ValueError(f"group domain {total} too large for direct "
                         f"aggregation; add a coarser key encoding")
    strides = []
    acc = 1
    for d in reversed(doms):
        strides.append(acc)
        acc *= d
    strides.reverse()
    return strides, max(total, 1)


def _combine_keys(keys: Sequence[jnp.ndarray], doms: Sequence[int]) -> jnp.ndarray:
    total = 1
    for d in doms:
        total *= d
    if total > int(_I32_MAX):
        raise ValueError("combined key domain exceeds int32; enable a "
                         "wider key encoding")
    out = keys[0].astype(jnp.int32)
    for k, d in zip(keys[1:], doms[1:]):
        out = out * np.int32(d) + k.astype(jnp.int32)
    return out


# ---------------------------------------------------------------------------
# phase A: build-side join index resolution (DESIGN.md section 10)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class JoinIndexSpec:
    """A join whose build side resolves to a cached base-table index.

    ``table``/``key_cols`` name the scan-level key columns the index is
    built over (after mapping the join's ``right_on`` names back through
    any Project renames); ``doms`` are the per-key combine domains (the
    same ``max(left, right)`` bounds the traced join uses, so cached and
    in-program combined keys agree bit-for-bit).  ``masked`` marks a
    filtered build side: the cached index covers the UNFILTERED table
    and the probe validates the matched row's filter mask post-probe --
    exact because the keys are unique (declared via ``Field.unique``,
    verified at index build time).  ``direct`` marks a dense combined
    key domain (every key declared, at most ``_DIRECT_SLOTS_PER_ROW``
    slots per build row): the program also takes the index's slot
    table and probes it with one gather instead of a binary search.
    """

    table: str
    key_cols: Tuple[str, ...]
    doms: Tuple[int, ...]
    masked: bool
    direct: bool

    @property
    def domain(self) -> int:
        """Size of the combined key domain: the slot table's length."""
        return math.prod(self.doms)

    @property
    def n_arrays(self) -> int:
        """How many index arrays the program takes for this join."""
        return 3 if self.direct else 2

    def arg_shapes(self, build_rows: int) -> Tuple[Tuple[int, ...], ...]:
        """Shapes of the int32 arrays the program takes for this join:
        ``perm`` and ``keys``, then the slot table when direct."""
        shapes = ((build_rows,), (build_rows,))
        return shapes + ((self.domain,),) if self.direct else shapes


def resolve_build_index(p: P.Join, catalog: P.Catalog
                        ) -> Tuple[Optional[JoinIndexSpec], str]:
    """Can this join's build side be served by a cached base-table
    index?  Returns ``(spec, reason)`` -- spec None when the join must
    keep its in-program argsort, with the reason for the report."""
    node = p.right
    mapping = {k: k for k in p.right_on}  # right_on name -> current name
    masked = False
    while not isinstance(node, P.Scan):
        if isinstance(node, P.Filter):
            masked = True
            node = node.child
            continue
        if isinstance(node, P.Project):
            outs = dict(node.outputs)
            new = {}
            for orig, cur in mapping.items():
                e = outs.get(cur)
                if isinstance(e, E.WithDomain):
                    e = e.arg  # domain annotations pass values through
                if not isinstance(e, E.Col):
                    return None, (f"build key {orig!r} is computed, not a "
                                  "base-table column")
                new[orig] = e.name
            mapping = new
            node = node.child
            continue
        return None, (f"build side is {node.describe()}, not a base-table "
                      "scan")
    tbl = catalog.table(node.table)
    if tbl.num_rows == 0:
        return None, "empty build table"
    key_cols = tuple(mapping[k] for k in p.right_on)
    left_i = static_info(p.left, catalog)
    right_i = static_info(p.right, catalog)
    ldoms = [left_i.cols[k].group_domain or int(_I32_MAX) for k in p.left_on]
    rdoms = [right_i.cols[k].group_domain or int(_I32_MAX) for k in p.right_on]
    doms = tuple(max(a, b) for a, b in zip(ldoms, rdoms))
    if len(key_cols) > 1 and any(d >= int(_I32_MAX) for d in doms):
        return None, "composite join keys need Field.domain bounds"
    if masked and not any(tbl.schema[c].unique for c in key_cols):
        return None, ("filtered build side without a declared-unique key "
                      "(Field.unique): post-probe mask validation would "
                      "be inexact under duplicate keys")
    # 'sortmerge' sorts the probe side on purpose (paper Fig. 6)
    direct = ((p.strategy or "sorted") != "sortmerge"
              and all(d < int(_I32_MAX) for d in doms)
              and math.prod(doms) <= _DIRECT_SLOTS_PER_ROW * tbl.num_rows)
    return JoinIndexSpec(node.table, key_cols, doms, masked, direct), "ok"


def join_index_plan(p: P.Plan, catalog: P.Catalog
                    ) -> Tuple[Dict[int, JoinIndexSpec],
                               List[Tuple[P.Join, Optional[JoinIndexSpec],
                                          str]]]:
    """Resolve every Join in ``p`` against the index cache.  Returns
    (id(join) -> spec for cache-served joins, per-join decisions in plan
    walk order for the dispatch report)."""
    specs: Dict[int, JoinIndexSpec] = {}
    decisions: List[Tuple[P.Join, Optional[JoinIndexSpec], str]] = []

    def rec(node: P.Plan):
        if isinstance(node, P.Join):
            spec, reason = resolve_build_index(node, catalog)
            if spec is not None:
                specs[id(node)] = spec
            decisions.append((node, spec, reason))
        for c in node.children():
            rec(c)

    rec(p)
    return specs, decisions


def index_stream_key(p: P.Join) -> Tuple[str, int]:
    """The ``scans``-dict key under which a join's cached index streams
    ride into the traced program (``build_callable`` populates it)."""
    return ("joinidx", id(p))


# ---------------------------------------------------------------------------
# phase B: traced operators
# ---------------------------------------------------------------------------


def _join_info(p: P.Join, left: StaticInfo, right: StaticInfo
               ) -> StaticInfo:
    """Output static info from the actual input streams (stream row
    counts may differ from catalog counts under sharded execution)."""
    if p.how in ("semi", "anti"):
        return left
    cols = dict(left.cols)
    for name, sc in right.cols.items():
        if name not in p.right_on:
            cols[name] = sc
    return StaticInfo(cols, left.n_rows)


#: A join's cached index stream: (perm, sorted keys, slot table or None).
JoinIndexStream = Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]


def _lower_join(p: P.Join, left: Stream, right: Stream,
                catalog: P.Catalog,
                jindex: Optional[JoinIndexStream] = None) -> Stream:
    """The join's own work under two device names: ``flare:join.probe``
    (combined keys, the build side's sort when no index is cached, the
    slot-table gather or ``searchsorted``, clip, match and validity
    masks) and ``flare:join.gather`` (the build-side column gathers)."""
    with OX.kernel_scope("flare:join.probe"):
        pos, matched, pmask = _join_probe(p, left, right, jindex)
        if p.how == "semi":
            return Stream(dict(left.cols), matched,
                          _join_info(p, left.info, right.info))
        if p.how == "anti":
            return Stream(dict(left.cols), pmask & ~matched,
                          _join_info(p, left.info, right.info))

    with OX.kernel_scope("flare:join.gather"):
        cols = dict(left.cols)
        for name in right.cols:
            if name in p.right_on:
                continue
            gathered = right.cols[name][pos]
            if p.how == "left":
                gathered = jnp.where(matched, gathered,
                                     jnp.zeros((), gathered.dtype))
            cols[name] = gathered
    mask = matched if p.how == "inner" else pmask
    return Stream(cols, mask, _join_info(p, left.info, right.info))


def _join_probe(p: P.Join, left: Stream, right: Stream,
                jindex: Optional[JoinIndexStream]
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(build-side row of each probe row's tentative match, the match
    mask, the probe side's mask)."""
    strategy = p.strategy or "sorted"
    # --- combined integer keys ------------------------------------------------
    ldoms = [left.info.cols[k].group_domain or int(_I32_MAX) for k in p.left_on]
    rdoms = [right.info.cols[k].group_domain or int(_I32_MAX) for k in p.right_on]
    doms = [max(a, b) for a, b in zip(ldoms, rdoms)]
    if len(p.left_on) > 1:
        for d in doms:
            if d >= int(_I32_MAX):
                raise TypeError("composite join keys need Field.domain bounds")
    kp = _combine_keys([left.cols[k] for k in p.left_on], doms)
    pmask = left.the_mask()

    # --- build side: the 'hash table' analogue --------------------------------
    slots = None
    if jindex is not None:
        # cached index (DESIGN.md section 10): the sorted permutation +
        # sorted keys (and, over a dense key domain, the slot table)
        # were built ONCE at preload/first use and enter the program as
        # arguments -- no in-program argsort.  The index covers the
        # unfiltered base table; a filtered build side is validated
        # post-probe against the matched row's mask (exact: keys are
        # unique, see resolve_build_index).
        perm, kb_sorted, slots = jindex
        validate_mask = right.mask
    else:
        kb = _combine_keys([right.cols[k] for k in p.right_on], doms)
        if right.mask is not None:
            kb = jnp.where(right.mask, kb, _I32_MAX)  # invalid rows never match
        perm = jnp.argsort(kb)
        kb_sorted = kb[perm]
        validate_mask = None

    if slots is not None:
        # slot table: slots[k] is the first build row (in stable sort
        # order) holding key k, -1 where none -- one gather a probe row
        OM.REGISTRY.inc("join.probe.direct")
        d = slots.shape[0]
        pos = slots[jnp.clip(kp, 0, d - 1)]
        matched = (kp >= 0) & (kp < d) & (pos >= 0) & pmask
        pos = jnp.maximum(pos, 0)
    else:
        OM.REGISTRY.inc("join.probe.sorted")
        if strategy == "sortmerge":
            # Paper Fig. 6: sort-merge also sorts the (large) probe side,
            # then un-permutes results -- strictly more work, kept for
            # comparison.
            probe_perm = jnp.argsort(kp)
            kp_s = kp[probe_perm]
            idx_s = jnp.searchsorted(kb_sorted, kp_s)
            inv = jnp.argsort(probe_perm)
            idx = idx_s[inv]
        else:
            idx = jnp.searchsorted(kb_sorted, kp)
        idx_c = jnp.clip(idx, 0, kb_sorted.shape[0] - 1)
        pos = perm[idx_c]  # build-table row of each (tentative) match
        matched = (kb_sorted[idx_c] == kp) & pmask
    if validate_mask is not None:
        matched = matched & validate_mask[pos]
    return pos, matched, pmask


def _lower_aggregate(p: P.Aggregate, child: Stream, catalog: P.Catalog,
                     params: Optional[Dict[str, Any]] = None) -> Stream:
    info = static_info(p, catalog)
    mask = child.the_mask()
    maskf = mask.astype(jnp.float32)

    def masked(vals, fill=None):
        if fill is None:
            # where, NOT multiply-by-mask: invalid rows may hold
            # arbitrary values (shard padding is zero-filled, so e.g. a
            # division yields inf/nan there) and nan * 0 would poison
            # the sum
            return jnp.where(mask, vals, jnp.zeros((), vals.dtype))
        return jnp.where(mask, vals, jnp.asarray(fill, vals.dtype))

    if not p.keys:  # global aggregate
        cols: Dict[str, jnp.ndarray] = {}
        cnt = jnp.sum(mask.astype(jnp.int32))
        for a in p.aggs:
            if a.op == "count":
                cols[a.name] = cnt[None]
                continue
            v = eval_expr(a.arg, child, params)
            if jnp.issubdtype(v.dtype, jnp.integer) and a.op in ("sum", "avg"):
                v = v.astype(jnp.float32)
            if a.op == "sum":
                cols[a.name] = jnp.sum(masked(v))[None]
            elif a.op == "avg":
                s = jnp.sum(masked(v))
                cols[a.name] = (s / jnp.maximum(cnt, 1))[None]
            elif a.op == "min":
                cols[a.name] = jnp.min(masked(v, _type_max(v.dtype)))[None]
            elif a.op == "max":
                cols[a.name] = jnp.max(masked(v, _type_min(v.dtype)))[None]
        return Stream(cols, None, info)

    strides, domain = _group_layout(p, child.info)
    code = jnp.zeros((child.n,), jnp.int32)
    for k, s in zip(p.keys, strides):
        code = code + child.cols[k].astype(jnp.int32) * np.int32(s)
    code = jnp.where(mask, code, 0)  # invalid rows land in group 0, masked out of counts

    cnt = jax.ops.segment_sum(mask.astype(jnp.int32), code,
                              num_segments=domain)
    cols = {}
    # decode key components from the group index
    gidx = jnp.arange(domain, dtype=jnp.int32)
    for k, s, in zip(p.keys, strides):
        dom_k = child.info.cols[k].group_domain
        cols[k] = (gidx // np.int32(s)) % np.int32(dom_k)
    for a in p.aggs:
        if a.op == "count":
            cols[a.name] = cnt
            continue
        v = eval_expr(a.arg, child, params)
        if jnp.issubdtype(v.dtype, jnp.integer) and a.op in ("sum", "avg"):
            v = v.astype(jnp.float32)
        if a.op == "sum":
            cols[a.name] = ML.segment_sum(masked(v), code, domain)
        elif a.op == "avg":
            s_ = ML.segment_sum(masked(v), code, domain)
            cols[a.name] = s_ / jnp.maximum(cnt, 1).astype(s_.dtype)
        elif a.op == "min":
            cols[a.name] = jax.ops.segment_min(
                masked(v, _type_max(v.dtype)), code, num_segments=domain)
        elif a.op == "max":
            cols[a.name] = jax.ops.segment_max(
                masked(v, _type_min(v.dtype)), code, num_segments=domain)
        elif a.op == "any":
            # FD carry-along: all members equal, take the max of valid ones.
            cols[a.name] = jax.ops.segment_max(
                masked(v, _type_min(v.dtype)), code, num_segments=domain
            ).astype(v.dtype)
    return Stream(cols, cnt > 0, info)


def _type_max(dt):
    return jnp.finfo(dt).max if jnp.issubdtype(dt, jnp.floating) else jnp.iinfo(dt).max


def _type_min(dt):
    return jnp.finfo(dt).min if jnp.issubdtype(dt, jnp.floating) else jnp.iinfo(dt).min


def _lower_sort(p: P.Sort, child: Stream, catalog: P.Catalog) -> Stream:
    mask = child.the_mask()
    # lexsort: last key is primary; invalid rows pushed to the end.
    keys = []
    for name, asc in reversed(p.by):
        v = child.cols[name]
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int32)
        if not asc:
            v = -v if jnp.issubdtype(v.dtype, jnp.signedinteger) or \
                jnp.issubdtype(v.dtype, jnp.floating) else v
        keys.append(v)
    keys.append((~mask).astype(jnp.int32))  # primary: valid first
    order = jnp.lexsort(tuple(keys))
    cols = {n: c[order] for n, c in child.cols.items()}
    return Stream(cols, mask[order], child.info)


def lower_node(p: P.Plan, catalog: P.Catalog, scans: Dict[int, Stream],
               params: Optional[Dict[str, Any]] = None) -> Stream:
    """Recursively lower ``p``; ``scans`` maps id(node) -> leaf Stream.

    Leaves are Scan nodes (whole-query compilation) or materialised stage
    outputs (stage-granular compilation, the Spark/Tungsten analogue).
    """
    if id(p) in scans:
        return scans[id(p)]
    # Custom-lowering protocol: plan nodes provided by subsystems outside
    # the core (e.g. repro.native's NativeOp kernel annotations) lower
    # themselves instead of growing this isinstance ladder.  Such a node
    # implements ``lower_stream(catalog, scans, params) -> Stream`` plus
    # ``static_info_hook(catalog)`` and ``required_columns_hook(rec,
    # needed)`` for the phase-A analyses.
    hook = getattr(p, "lower_stream", None)
    if hook is not None:
        return hook(catalog, scans, params)
    if isinstance(p, P.Scan):
        raise KeyError(f"unbound scan {p.table}")
    if isinstance(p, P.Filter):
        child = lower_node(p.child, catalog, scans, params)
        with OX.kernel_scope("flare:filter"):
            pred = eval_expr(p.pred, child, params)
            mask = pred if child.mask is None else (child.mask & pred)
        return Stream(child.cols, mask, child.info)
    if isinstance(p, P.MapBatches):
        child = lower_node(p.child, catalog, scans, params)
        outs = p.fn({c: child.cols[c] for c in p.columns})
        if set(outs) != set(p.out_names):
            raise TypeError(
                f"map_batches {p.name!r} returned columns "
                f"{sorted(outs)}, declared schema is "
                f"{sorted(p.out_names)}")
        produced = set(p.out_names)
        cols = {n: v for n, v in child.cols.items() if n not in produced}
        scols = {n: sc for n, sc in child.info.cols.items()
                 if n not in produced}
        for f in p.out_fields:
            v = jnp.asarray(outs[f.name])
            if v.shape != (child.n,):
                raise TypeError(
                    f"map_batches {p.name!r} output {f.name!r} has shape "
                    f"{v.shape}; expected ({child.n},) -- batch UDFs must "
                    "be length-preserving 1-D columns")
            cols[f.name] = v.astype(_JNP_OF[f.dtype])
            scols[f.name] = StaticCol(f.dtype, None, f.domain)
        return Stream(cols, child.mask, StaticInfo(scols, child.n))
    if isinstance(p, P.Project):
        child = lower_node(p.child, catalog, scans, params)
        cols = {name: eval_expr(e, child, params) for name, e in p.outputs}
        schema = p.child.schema(catalog)
        scols = {}
        for name, e in p.outputs:
            if isinstance(e, E.Col):
                scols[name] = child.info.cols[e.name]
            elif isinstance(e, E.WithDomain):
                inner = (child.info.cols[e.arg.name]
                         if isinstance(e.arg, E.Col)
                         else StaticCol(E.infer_dtype(e.arg, schema)))
                scols[name] = StaticCol(inner.dtype, inner.dictionary,
                                        e.domain)
            else:
                scols[name] = StaticCol(E.infer_dtype(e, schema))
        return Stream(cols, child.mask, StaticInfo(scols, child.n))
    if isinstance(p, P.Join):
        left = lower_node(p.left, catalog, scans, params)
        right = lower_node(p.right, catalog, scans, params)
        return _lower_join(p, left, right, catalog,
                           scans.get(index_stream_key(p)))
    if isinstance(p, P.Aggregate):
        child = lower_node(p.child, catalog, scans, params)
        with OX.kernel_scope("flare:agg"):
            return _lower_aggregate(p, child, catalog, params)
    if isinstance(p, P.Sort):
        child = lower_node(p.child, catalog, scans, params)
        with OX.kernel_scope("flare:sort"):
            return _lower_sort(p, child, catalog)
    if isinstance(p, P.Limit):
        child = lower_node(p.child, catalog, scans, params)
        n = min(p.n, child.n)
        cols = {c_: c[:n] for c_, c in child.cols.items()}
        mask = None if child.mask is None else child.mask[:n]
        return Stream(cols, mask, StaticInfo(child.info.cols, n))
    raise TypeError(f"cannot lower plan node {p!r}")


# ---------------------------------------------------------------------------
# heterogeneous handoff: relational stream -> matrix -> training kernel
# ---------------------------------------------------------------------------


def resolve_hyper(p: "P.IterativeKernel",
                  params: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Bind the kernel's hyper-parameters: Param placeholders pull their
    (possibly traced) runtime value from ``params``; literals pass
    through.  Shape-affecting hypers (e.g. k-means ``k``) must be
    literals -- a Param there fails inside the kernel, by design."""
    out: Dict[str, Any] = {}
    for k, v in p.hyper:
        if isinstance(v, E.Param):
            if params is None or v.name not in params:
                raise KeyError(
                    f"unbound hyper-parameter {v.name!r} of kernel "
                    f"{p.kernel.name}; pass a binding, e.g. "
                    f"compiled({v.name}=...)")
            out[k] = params[v.name]
        elif isinstance(v, E.Expr):
            raise TypeError(
                f"hyper-parameter {k!r} of {p.kernel.name} must be a "
                f"literal or param(), got expression {v!r}")
        else:
            out[k] = v
    return out


def apply_kernel(p: "P.IterativeKernel", stream: Stream,
                 params: Optional[Dict[str, Any]] = None):
    """Stack the feature columns of ``stream`` into an [n, d] float32
    matrix and run the training kernel on it -- traced, so under the
    whole-query engine the relational operators and the kernel's
    ``lax.while_loop`` land in ONE program (paper Fig. 8).

    The validity mask becomes the kernel's sample weights and invalid
    rows are zeroed (their padded contents are unspecified), so the
    padded result equals the compacted interpreters' result.
    """
    mask = stream.the_mask()
    w = mask.astype(jnp.float32)
    x = jnp.stack([stream.cols[c].astype(jnp.float32) for c in p.features],
                  axis=1)
    x = x * w[:, None]
    y = None
    if p.label is not None:
        y = stream.cols[p.label].astype(jnp.float32) * w
    return p.kernel(x, y, weights=w, **resolve_hyper(p, params))


# ---------------------------------------------------------------------------
# whole-query compilation entry point
# ---------------------------------------------------------------------------


def required_scan_columns(p: P.Plan, catalog: P.Catalog) -> Dict[int, List[str]]:
    """Columns each Scan must bind (after optimizer pruning, this is small)."""
    out: Dict[int, List[str]] = {}

    def rec(node: P.Plan, needed: Optional[set]):
        if isinstance(node, P.Scan):
            names = node.schema(catalog).names
            cols = [n for n in names if needed is None or n in needed]
            out[id(node)] = cols or names[:1]
            return
        if isinstance(node, P.Filter):
            need = None if needed is None else set(needed) | set(E.columns_of(node.pred))
            rec(node.child, need)
        elif isinstance(node, P.Project):
            # NOTE: lower_node evaluates every Project output, so every
            # output's inputs are required; dropping unused *outputs* is an
            # optimizer rewrite (prune_projections), not a binding decision.
            need = set()
            for name, e in node.outputs:
                need |= set(E.columns_of(e))
            rec(node.child, need)
        elif isinstance(node, P.Join):
            lneed = None if needed is None else set()
            rneed = None if needed is None else set()
            if needed is not None:
                lnames = set(node.left.schema(catalog).names)
                for n in needed:
                    (lneed if n in lnames else rneed).add(n)
                lneed |= set(node.left_on)
                rneed |= set(node.right_on)
            else:
                pass
            rec(node.left, lneed)
            rec(node.right, rneed if node.how not in ("semi", "anti")
                else (None if needed is None else set(node.right_on)))
        elif isinstance(node, P.Aggregate):
            need = set(node.keys)
            for a in node.aggs:
                if a.arg is not None:
                    need |= set(E.columns_of(a.arg))
            rec(node.child, need)
        elif isinstance(node, (P.Sort, P.Limit)):
            need = needed
            if isinstance(node, P.Sort) and needed is not None:
                need = set(needed) | {n for n, _ in node.by}
            rec(node.child, need)
        elif isinstance(node, P.MapBatches):
            if needed is None:
                need = None  # every pass-through column may be consumed
            else:
                need = ((set(needed) - set(node.out_names))
                        | set(node.columns))
            rec(node.child, need)
        elif isinstance(node, P.IterativeKernel):
            rec(node.child, set(node.required_columns()))
        elif hasattr(node, "required_columns_hook"):
            node.required_columns_hook(rec, needed)
        else:
            raise TypeError(node)

    rec(p, None)
    return out


def scan_paths(p: P.Plan) -> Dict[int, Tuple[int, ...]]:
    """Map ``id(Scan)`` -> root-to-scan child-index path.

    The path is a *structural* identity: it survives plan rebuilds
    (optimizer rewrites, ``with_children`` copies) that change every
    node's address, so it is the right key to hand to observability
    layers that outlive the plan object they were computed from.
    """
    out: Dict[int, Tuple[int, ...]] = {}

    def rec(node: P.Plan, path: Tuple[int, ...]) -> None:
        if isinstance(node, P.Scan):
            out[id(node)] = path
        for i, c in enumerate(node.children()):
            rec(c, path + (i,))

    rec(p, ())
    return out


def required_scan_columns_by_path(
        p: P.Plan, catalog: P.Catalog) -> Dict[Tuple[int, ...], List[str]]:
    """:func:`required_scan_columns`, keyed by child-index path instead
    of ``id(node)`` -- stable across plan copies and GC address reuse."""
    needed = required_scan_columns(p, catalog)
    paths = scan_paths(p)
    return {paths[sid]: cols for sid, cols in needed.items()
            if sid in paths}


@dataclasses.dataclass
class Result:
    """Execution result: padded columns + validity mask + schema."""

    cols: Dict[str, np.ndarray]
    mask: Optional[np.ndarray]
    schema: T.Schema
    dicts: Dict[str, Optional[Tuple[str, ...]]]
    ordered: bool = True

    def num_rows(self) -> int:
        if self.mask is None:
            return len(next(iter(self.cols.values())))
        return int(self.mask.sum())

    def compact(self) -> Dict[str, np.ndarray]:
        """Valid rows only, strings decoded, host dtypes per schema.
        The ``fetch`` span covers the copy of columns still on the
        device to the host and their selection and decoding."""
        with OT.span("fetch", cols=len(self.schema)):
            if self.mask is None:
                sel = slice(None)
            else:
                sel = np.flatnonzero(self.mask)
            out = {}
            for f in self.schema:
                arr = np.asarray(self.cols[f.name])[sel]
                d = self.dicts.get(f.name)
                if d is not None:
                    lut = np.asarray(d, dtype=object)
                    out[f.name] = lut[arr]
                elif f.dtype == T.STRING and arr.dtype == object:
                    out[f.name] = arr  # decoded strings (tuple engine)
                else:
                    out[f.name] = arr.astype(T.numpy_dtype(f.dtype))
        return out

    def scalar(self, name: Optional[str] = None):
        c = self.compact()
        if name is None:
            name = next(iter(c))
        return c[name][0]


@dataclasses.dataclass
class ValueResult:
    """Non-relational execution result: the output pytree of a plan
    rooted at :class:`repro.core.plan.IterativeKernel` (e.g. a
    ``KMeansResult``).  Quacks enough like :class:`Result` for the
    stages API -- ``compact()`` is the identity on the value."""

    value: Any

    def compact(self):
        return self.value

    def num_rows(self) -> int:
        raise TypeError("a trained-kernel result has no row count; "
                        "use .value / compact()")

    def scalar(self, name: Optional[str] = None):
        raise TypeError("a trained-kernel result has no scalar columns; "
                        "use .value / compact()")


def build_callable(p: P.Plan, catalog: P.Catalog,
                   param_specs: Sequence[E.Param] = (),
                   scan_stream_fn: Optional[Callable[..., Stream]] = None
                   ) -> Tuple[Callable[..., Any], List[Tuple[int, List[str]]],
                              List[JoinIndexSpec], Optional[StaticInfo]]:
    """Build the pure function over flat scan-column arrays.

    Returns (fn, arg_layout, index_layout, out_info) where arg_layout
    lists (scan_node_id, column_names) in argument order.  If
    ``param_specs`` is non-empty, ``fn`` takes one trailing scalar
    argument per spec (in spec order) -- the runtime values of
    :class:`repro.core.expr.Param` placeholders, traced rather than
    baked into the program.

    ``index_layout`` lists the :class:`JoinIndexSpec` of every join
    whose build side is served by the cached base-table index (DESIGN.md
    section 10): between the scan columns and the params, ``fn`` takes
    the int32 arrays of :meth:`JoinIndexSpec.arg_shapes` per entry, in
    layout order: (perm, sorted keys), plus the slot table for a direct
    spec.  Engines fetch those from :class:`repro.core.engines.IndexCache`
    at call time, so the "hash table" is built at load time and the
    program only probes.  Setting ``p._join_index_disabled`` (the
    ``lower(join_index=False)`` escape hatch) keeps every join on its
    in-program argsort.

    ``scan_stream_fn(scan_node, cols, static)``, when given, builds the
    leaf :class:`Stream` for each Scan instead of the default (full
    catalog-length, unmasked) construction.  The sharded ``parallel``
    engine uses this to run the SAME traced function per mesh shard:
    leaf streams take their row count from the actual (shard-local)
    arrays and the partitioned spine scan carries a validity mask for
    its padding rows (DESIGN.md section 9).

    For a relational plan ``fn`` returns ``(out_cols, mask)``.  For a
    plan rooted at :class:`repro.core.plan.IterativeKernel` -- the
    heterogeneous-pipeline case -- ``fn`` returns the kernel's result
    pytree instead, the relational half flowing straight into the
    training loop within the same trace (``out_info`` is None).
    """
    needed = required_scan_columns(p, catalog)
    scan_nodes: List[P.Scan] = []

    def collect(node: P.Plan):
        if isinstance(node, P.Scan):
            scan_nodes.append(node)
        for c in node.children():
            collect(c)

    collect(p)
    layout = [(id(s), needed[id(s)]) for s in scan_nodes]
    statics = {id(s): _static_of_scan(catalog.table(s.table))
               for s in scan_nodes}
    if getattr(p, "_join_index_disabled", False):
        index_specs: Dict[int, JoinIndexSpec] = {}
    else:
        index_specs, _ = join_index_plan(p, catalog)
    index_items = list(index_specs.items())  # plan-walk order = arg order
    index_layout = [spec for _, spec in index_items]
    ml_root = isinstance(p, P.IterativeKernel)
    out_info = None if ml_root else static_info(p, catalog)
    param_specs = tuple(param_specs)

    def fn(*flat_arrays):
        it = iter(flat_arrays)
        scans: Dict[Any, Any] = {}
        for s in scan_nodes:
            cols = {name: next(it) for name in needed[id(s)]}
            static = StaticInfo(
                {n: statics[id(s)].cols[n] for n in needed[id(s)]},
                statics[id(s)].n_rows)
            if scan_stream_fn is not None:
                scans[id(s)] = scan_stream_fn(s, cols, static)
            else:
                scans[id(s)] = Stream(cols, None, static)
        for jid, spec in index_items:
            perm, keys = next(it), next(it)
            slots = next(it) if spec.direct else None
            scans[("joinidx", jid)] = (perm, keys, slots)
        env = {spec.name: next(it) for spec in param_specs}
        if ml_root:
            stream = lower_node(p.child, catalog, scans, env or None)
            return apply_kernel(p, stream, env or None)
        stream = lower_node(p, catalog, scans, env or None)
        out_cols = {n: stream.cols[n] for n in p.schema(catalog).names}
        return out_cols, (stream.the_mask())

    return fn, layout, index_layout, out_info


def build_batch_callable(p: P.Plan, catalog: P.Catalog,
                         param_specs: Sequence[E.Param],
                         ) -> Tuple[Callable[..., Any],
                                    List[Tuple[int, List[str]]],
                                    List[JoinIndexSpec],
                                    Optional[StaticInfo]]:
    """Build the vmap-coalesced variant of :func:`build_callable`.

    The multi-tenant serving insight (DESIGN.md section 11): all
    bindings of one prepared template run the SAME program over the
    SAME tables -- only the ``param()`` scalars differ -- so a queue of
    B same-template requests is ONE batched program, not B dispatches.
    The returned function takes the identical scan-column and
    join-index arguments as the single-binding callable (shared inputs,
    broadcast across the batch: ``in_axes=None``) plus one ``[B]``
    array per param spec (the stacked bindings, ``in_axes=0``); every
    output gains a leading ``[B]`` axis.

    vmap keeps the sharing real, not just notational: operators that do
    not depend on a param (scans, index probes of param-free joins,
    dictionary gathers) stay unbatched inside the program, and only the
    param-dependent dataflow fans out over the batch axis.

    Raises for a param-free template: with no binding axis to vmap
    over, every request IS the same execution -- run it once and share
    the result (``repro.core.stages.Compiled.batch`` does exactly
    that).
    """
    param_specs = tuple(param_specs)
    if not param_specs:
        raise ValueError(
            "build_batch_callable needs param() placeholders; a "
            "param-free template has no binding axis -- execute it once "
            "and share the result across requests")
    fn, layout, index_layout, out_info = build_callable(p, catalog,
                                                        param_specs)
    n_shared = (sum(len(names) for _, names in layout)
                + sum(s.n_arrays for s in index_layout))
    in_axes = (None,) * n_shared + (0,) * len(param_specs)
    return jax.vmap(fn, in_axes=in_axes), layout, index_layout, out_info
