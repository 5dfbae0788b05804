"""Built-in kernel patterns: plan fragments the Pallas kernels can serve.

Four patterns register at import (HiFrames-style pattern matching of
dataframe plan fragments onto specialized parallel implementations):

* ``filter-scalar-agg``    -- keyless Aggregate over a Filter/Project
  prologue rooted at a Scan: the paper's Fig. 3 Q6 loop, generalized.
  The predicate tree and the aggregate value expressions are compiled
  into the kernel body; :func:`repro.core.expr.param` placeholders
  become *scalar-prefetch* runtime arguments, so a prepared template
  (q6 and friends) stays ONE compilation across bindings.
* ``grouped-agg``          -- keyed Aggregate over the same prologue,
  lowered onto the segmented reduction (``kernels/segmented_reduce``),
  multi-aggregate: every sum/count/avg/any accumulates in one pass over
  the dense group layout ``lower.py`` already computes, per-group
  membership masks into a VMEM-resident accumulator (the FD ``any_``
  carry-along rides as a masked per-group max).
* ``join-probe``           -- Aggregate whose boundary is an inner N:1
  join served by the cached build-side index (DESIGN.md section 10):
  binary-search probe + payload gather + residual predicate + partial
  aggregate fuse into ONE Pallas pass (``kernels/join_probe``).  The
  cached sorted keys/permutation enter as whole-array kernel inputs;
  group domains beyond the dense accumulator use a scatter accumulator
  (TPC-H q3's ~15k l_orderkey groups).  Interpret mode only: the probe
  is a data-dependent gather Mosaic cannot lower, so on a TPU the
  pattern is refused with a recorded reason (``pallas_refusal``).
* ``masked-filter-project`` -- the scalar/grouped shapes sitting
  mid-pipeline (boundary stream carries a validity mask, e.g.
  downstream of a non-inner or non-indexed join): the mask streams into
  the kernel as a weight column and the same emitters apply.

Expression support inside the kernel body mirrors the compiled engine's
TPU-legal lowering: arithmetic/comparison/boolean trees, dictionary-code
comparisons against string literals, ``isin`` as code tests, and string
predicates evaluated on the (sorted) dictionary at dispatch time and
baked in as *code ranges*.  Anything else (LUT gathers that will not
vectorise, staged UDFs, truncating int casts) makes the fragment
ineligible -- it keeps its generic jnp lowering and the dispatch report
says why.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

import jax.numpy as jnp

from repro.core import expr as E
from repro.core import lower as L
from repro.core import plan as P
from repro.kernels.filter_agg import kernel as FA_K
from repro.kernels.filter_agg import ops as FA_OPS
from repro.kernels.join_probe import kernel as JP_K
from repro.kernels.segmented_reduce import kernel as SR_K
from repro.native import registry as R
from repro.relational import table as T

LANES = R.LANES

#: Largest f32-exactly-representable integer: int columns streamed into a
#: kernel are cast to f32, so their domain must stay below this.
F32_EXACT = 1 << 24

#: A string predicate whose dictionary LUT fragments into more code
#: ranges than this is cheaper as the generic LUT gather -- fall back.
MAX_STRPRED_RANGES = 16


class UnsupportedExpr(TypeError):
    """Expression form the kernel body cannot express; fragment falls
    back to the generic jnp lowering (recorded in the dispatch report)."""


class _NoMatch(Exception):
    """Structural mismatch while walking a fragment (not an error)."""


# ---------------------------------------------------------------------------
# expression tree -> kernel-body closure
# ---------------------------------------------------------------------------

_CMP_OPS = {"<": jnp.less, "<=": jnp.less_equal, ">": jnp.greater,
            ">=": jnp.greater_equal, "==": jnp.equal, "!=": jnp.not_equal}
_FLIP = {"<": ">", ">": "<", "<=": ">=", ">=": "<=", "==": "==", "!=": "!="}


def _as_bool(x):
    """Coerce an f32 0/1 column (bool columns stream as f32) to bool."""
    if hasattr(x, "dtype") and x.dtype == jnp.bool_:
        return x
    return x > 0.5


class ExprCompiler:
    """Compile an expression tree (in boundary-column terms) into a
    closure ``fn(cols, scal) -> block`` evaluated *inside* the kernel
    body, where ``cols`` maps column name -> [rows, 128] f32 block and
    ``scal`` maps param name -> scalar-prefetch value.

    Dictionary contents come from the boundary's phase-A static info, so
    string comparisons resolve to integer code tests at dispatch time --
    the same specialization the whole-query engine bakes in, now baked
    into a Pallas kernel.  Referenced columns and params are collected
    on ``self.cols`` / ``self.params`` for the emitter's input layout.
    """

    def __init__(self, binfo: L.StaticInfo):
        self.binfo = binfo
        self.schema = T.Schema([T.Field(n, sc.dtype, sc.domain)
                                for n, sc in binfo.cols.items()])
        self.cols: Set[str] = set()
        self.params: Set[str] = set()

    # -- helpers ---------------------------------------------------------------

    def _dict_of(self, e: E.Expr):
        if isinstance(e, E.Col):
            return self.binfo.cols[e.name].dictionary
        return None

    def compile(self, e: E.Expr) -> Callable[[Dict, Dict], Any]:
        if isinstance(e, E.Col):
            self.cols.add(e.name)
            name = e.name
            return lambda cols, scal: cols[name]
        if isinstance(e, E.Lit):
            if isinstance(e.value, str):
                raise UnsupportedExpr("string literal outside comparison")
            v = float(e.value)
            return lambda cols, scal: v
        if isinstance(e, E.Param):
            self.params.add(e.name)
            name = e.name
            return lambda cols, scal: scal[name]
        if isinstance(e, E.BinOp):
            lf, rf = self.compile(e.left), self.compile(e.right)
            op = e.op
            if op == "+":
                return lambda cols, scal: lf(cols, scal) + rf(cols, scal)
            if op == "-":
                return lambda cols, scal: lf(cols, scal) - rf(cols, scal)
            if op == "*":
                return lambda cols, scal: lf(cols, scal) * rf(cols, scal)
            if op == "/":
                # everything streams as f32: true division, like the
                # compiled engine's float-promoting "/"
                return lambda cols, scal: lf(cols, scal) / rf(cols, scal)
            raise UnsupportedExpr(f"binop {op!r}")
        if isinstance(e, E.Cmp):
            return self._compile_cmp(e)
        if isinstance(e, E.BoolOp):
            fns = [self.compile(a) for a in e.args]
            is_and = e.op == "and"

            def run_bool(cols, scal):
                out = _as_bool(fns[0](cols, scal))
                for fn in fns[1:]:
                    v = _as_bool(fn(cols, scal))
                    out = (out & v) if is_and else (out | v)
                return out

            return run_bool
        if isinstance(e, E.Not):
            f = self.compile(e.arg)
            return lambda cols, scal: ~_as_bool(f(cols, scal))
        if isinstance(e, E.InSet):
            return self._compile_inset(e)
        if isinstance(e, E.StrPred):
            return self._compile_strpred(e)
        if isinstance(e, E.IfThenElse):
            cf = self.compile(e.cond)
            tf, of = self.compile(e.then), self.compile(e.other)
            return lambda cols, scal: jnp.where(_as_bool(cf(cols, scal)),
                                                tf(cols, scal),
                                                of(cols, scal))
        if isinstance(e, E.Cast):
            src = E.infer_dtype(e.arg, self.schema)
            if e.dtype in (T.INT32, T.INT64, T.DATE) and \
                    src in (T.FLOAT32, T.FLOAT64):
                raise UnsupportedExpr("truncating float->int cast")
            f = self.compile(e.arg)
            if e.dtype == T.BOOL and src != T.BOOL:
                # astype(bool) is `!= 0`, NOT the 0/1-column `> 0.5`
                # coercion _as_bool applies to stored bool columns
                return lambda cols, scal: f(cols, scal) != 0
            # numeric casts are identities: all kernel values are f32
            return f
        if isinstance(e, E.WithDomain):
            return self.compile(e.arg)
        raise UnsupportedExpr(type(e).__name__)

    def _compile_cmp(self, e: E.Cmp):
        ldict, rdict = self._dict_of(e.left), self._dict_of(e.right)
        if ldict is not None and isinstance(e.right, E.Lit) \
                and isinstance(e.right.value, str):
            return self._code_cmp(e.op, self.compile(e.left), ldict,
                                  e.right.value)
        if rdict is not None and isinstance(e.left, E.Lit) \
                and isinstance(e.left.value, str):
            return self._code_cmp(_FLIP[e.op], self.compile(e.right), rdict,
                                  e.left.value)
        if ldict is not None and rdict is not None and ldict != rdict:
            raise UnsupportedExpr("cross-dictionary string comparison")
        lf, rf = self.compile(e.left), self.compile(e.right)
        opf = _CMP_OPS[e.op]
        return lambda cols, scal: opf(lf(cols, scal), rf(cols, scal))

    def _code_cmp(self, op: str, codes_fn, dictionary, value: str):
        """String-literal comparison as an integer code test (codes are
        in sorted-dictionary == lexical order), absent-literal semantics
        identical to ``lower._cmp_with_code``."""
        code = L._str_code(dictionary, value)
        if code < 0:
            if op == "==":
                return lambda cols, scal: jnp.zeros_like(
                    codes_fn(cols, scal), jnp.bool_)
            if op == "!=":
                return lambda cols, scal: jnp.ones_like(
                    codes_fn(cols, scal), jnp.bool_)
            ins = float(np.searchsorted(np.asarray(dictionary, dtype=object),
                                        value))
            if op in ("<", "<="):
                return lambda cols, scal: codes_fn(cols, scal) < ins
            return lambda cols, scal: codes_fn(cols, scal) >= ins
        opf = _CMP_OPS[op]
        c = float(code)
        return lambda cols, scal: opf(codes_fn(cols, scal), c)

    def _compile_inset(self, e: E.InSet):
        d = self._dict_of(e.arg)
        arg_fn = self.compile(e.arg)
        if d is not None:
            vals = [float(c) for c in (L._str_code(d, v) for v in e.values)
                    if c >= 0]
            if not vals:
                return lambda cols, scal: jnp.zeros_like(
                    arg_fn(cols, scal), jnp.bool_)
        else:
            if any(isinstance(v, str) for v in e.values):
                raise UnsupportedExpr("isin(strings) on non-dict column")
            vals = [float(v) for v in e.values]

        def run_inset(cols, scal):
            a = arg_fn(cols, scal)
            out = a == vals[0]
            for v in vals[1:]:
                out = out | (a == v)
            return out

        return run_inset

    def _compile_strpred(self, e: E.StrPred):
        d = self._dict_of(e.arg)
        if d is None:
            raise UnsupportedExpr(f"{e.kind} on non-string column")
        lut = [L._match_str(e.kind, s, e.params) for s in d]
        ranges = _lut_ranges(lut)
        if len(ranges) > MAX_STRPRED_RANGES:
            raise UnsupportedExpr(
                f"{e.kind} LUT fragments into {len(ranges)} code ranges")
        arg_fn = self.compile(e.arg)

        def run_strpred(cols, scal):
            a = arg_fn(cols, scal)
            out = jnp.zeros_like(a, jnp.bool_)
            for lo, hi in ranges:
                if hi == lo + 1:
                    out = out | (a == float(lo))
                else:
                    out = out | ((a >= float(lo)) & (a < float(hi)))
            return out

        return run_strpred


def _lut_ranges(lut: List[bool]) -> List[Tuple[int, int]]:
    """Maximal [lo, hi) runs of True in a boolean dictionary LUT.  The
    dictionary is sorted, so prefix predicates compress to ONE range."""
    ranges: List[Tuple[int, int]] = []
    i, n = 0, len(lut)
    while i < n:
        if lut[i]:
            j = i
            while j < n and lut[j]:
                j += 1
            ranges.append((i, j))
            i = j
        else:
            i += 1
    return ranges


# ---------------------------------------------------------------------------
# fragment matching
# ---------------------------------------------------------------------------

_PROLOGUE = (P.Filter, P.Project)


def boundary_of(root: P.Plan) -> P.Plan:
    """First non-Filter/Project descendant below an Aggregate root: the
    node whose stream the kernel consumes."""
    node = root.child if isinstance(root, P.Aggregate) else root
    while isinstance(node, _PROLOGUE):
        node = node.child
    return node


def match_fragment(node: P.Plan, catalog: P.Catalog) -> Optional[R.Fragment]:
    """Walk the Filter/Project prologue under an Aggregate and rebase
    every expression (filter conjuncts, aggregate args, group keys) onto
    boundary-column terms.  Returns None on structural mismatch."""
    if not isinstance(node, P.Aggregate):
        return None
    chain: List[P.Plan] = []
    cur = node.child
    while isinstance(cur, _PROLOGUE):
        chain.append(cur)
        cur = cur.child
    boundary = cur
    try:
        binfo = L.static_info(boundary, catalog)
    except TypeError:
        return None
    mapping: Dict[str, E.Expr] = {n: E.col(n) for n in binfo.cols}

    def sub(e: E.Expr) -> E.Expr:
        def repl(x: E.Expr) -> Optional[E.Expr]:
            if isinstance(x, E.Col):
                if x.name not in mapping:
                    raise _NoMatch()
                return mapping[x.name]
            return None

        return E.map_expr(e, repl)

    preds: List[E.Expr] = []
    try:
        for nd in reversed(chain):
            if isinstance(nd, P.Filter):
                preds.append(sub(nd.pred))
            else:
                mapping = {name: sub(expr) for name, expr in nd.outputs}
        agg_args = tuple(sub(a.arg) if a.arg is not None else None
                         for a in node.aggs)
        for k in node.keys:
            if k not in mapping:
                raise _NoMatch()
        key_exprs = tuple(mapping[k] for k in node.keys)
    except _NoMatch:
        return None
    return R.Fragment(root=node, boundary=boundary, preds=tuple(preds),
                      agg_args=agg_args, key_exprs=key_exprs,
                      masked=not isinstance(boundary, P.Scan), binfo=binfo)


#: Sentinel distinguishing "caller did not pre-compute the walk" from
#: "the walk ran and found no fragment" (an explicit None must NOT
#: trigger a re-walk -- the dispatch pass shares one walk per node).
_UNSET = object()


def _match_scalar(node, catalog, frag=_UNSET):
    if frag is _UNSET:
        frag = match_fragment(node, catalog)
    if frag is None or frag.root.keys or frag.masked:
        return None
    return frag


def _match_grouped(node, catalog, frag=_UNSET):
    if frag is _UNSET:
        frag = match_fragment(node, catalog)
    if frag is None or not frag.root.keys or frag.masked:
        return None
    return frag


def _match_masked(node, catalog, frag=_UNSET):
    if frag is _UNSET:
        frag = match_fragment(node, catalog)
    if frag is None or not frag.masked:
        return None
    return frag


# ---------------------------------------------------------------------------
# eligibility
# ---------------------------------------------------------------------------

_SUPPORTED_AGGS = ("sum", "count", "avg")
#: ``any`` (the FD carry-along: all group members share the value) is
#: grouped-only, accumulated as a per-group masked max.
_SUPPORTED_GROUPED_AGGS = _SUPPORTED_AGGS + ("any",)

#: ``any`` max-slot neutral element, by value class.  INT32_MIN is
#: f32-exact AND converts back to int32 exactly, so the (masked-out)
#: empty-group sentinel survives the f32 kernel -> int column cast; the
#: float fill mirrors the generic lowering's finfo.min.
_INT_ANY_FILL = float(np.iinfo(np.int32).min)
_FLOAT_ANY_FILL = float(np.finfo(np.float32).min)


def _any_fill(dtype: str) -> float:
    return (_FLOAT_ANY_FILL if dtype in (T.FLOAT32, T.FLOAT64)
            else _INT_ANY_FILL)


def _col_f32_safe(sc: L.StaticCol) -> bool:
    """Can this column stream into the kernel as exact f32?  Floats and
    bools trivially; dates are bounded days-since-1970 (< 2^24 by
    construction); other ints need a dictionary or declared domain."""
    if sc.dtype in (T.FLOAT32, T.FLOAT64, T.BOOL, T.DATE):
        return True
    bound = sc.group_domain
    return bound is not None and bound <= F32_EXACT


def _acc_plan(aggs: Tuple[P.AggSpec, ...], force_count: bool
              ) -> Tuple[List[Tuple[str, Optional[int]]], Optional[int],
                         int, Tuple[str, ...]]:
    """Accumulator layout: one slot per sum/avg/any argument plus ONE
    shared count slot (grouped fragments always count: the group mask
    needs it).  Returns (per-agg plan, count slot index, slot count,
    per-slot accumulate op: "sum" or "max")."""
    plan: List[Tuple[str, Optional[int]]] = []
    ops: List[str] = []
    k = 0
    for a in aggs:
        if a.op in ("sum", "avg"):
            plan.append((a.op, k))
            ops.append("sum")
            k += 1
        elif a.op == "any":
            plan.append(("any", k))
            ops.append("max")
            k += 1
        else:
            plan.append(("count", None))
    need_count = force_count or any(a.op in ("count", "avg") for a in aggs)
    cnt_slot = k if need_count else None
    if need_count:
        ops.append("sum")
    return plan, cnt_slot, (k + 1 if need_count else k), tuple(ops)


@dataclasses.dataclass
class _Analysis:
    """Everything static the emitter needs, computed ONCE per fragment
    (memoized on ``Fragment.analysis``): compiled expression closures,
    accumulator plan, input-column layout, group layout, block shape --
    or the reason the fragment is ineligible."""

    reason: Optional[str] = None  # None = eligible
    plan_: Any = None
    cnt_slot: Optional[int] = None
    n_out: int = 0
    ops: Tuple[str, ...] = ()
    fills: Tuple[float, ...] = ()
    pred_fns: Any = None
    val_fns: Any = None
    col_names: Any = None
    param_names: Any = None
    strides: Any = None
    domain: Optional[int] = None
    key_doms: Any = None
    block_default: Optional[int] = None


def _slot_fills(aggs: Tuple[P.AggSpec, ...], schema: T.Schema,
                cnt_slot: Optional[int]) -> Tuple[float, ...]:
    """Per-slot accumulator fill: 0 for sums, the dtype-dependent
    ``any`` neutral element for max slots."""
    fills: List[float] = []
    for a in aggs:
        if a.op in ("sum", "avg"):
            fills.append(0.0)
        elif a.op == "any":
            fills.append(_any_fill(E.infer_dtype(a.arg, schema)))
    if cnt_slot is not None:
        fills.append(0.0)
    return tuple(fills)


def _analyze(frag: R.Fragment, catalog: P.Catalog) -> _Analysis:
    if frag.analysis is not None:
        return frag.analysis
    frag.analysis = out = _analyze_uncached(frag, catalog)
    return out


def _analyze_uncached(frag: R.Fragment, catalog: P.Catalog) -> _Analysis:
    grouped = bool(frag.root.keys)
    supported = _SUPPORTED_GROUPED_AGGS if grouped else _SUPPORTED_AGGS
    bad = sorted({a.op for a in frag.root.aggs if a.op not in supported})
    if bad:
        return _Analysis(reason=f"unsupported aggregate op(s) {bad}")
    if frag.binfo.n_rows <= 0:
        return _Analysis(reason="empty input stream")
    plan_, cnt_slot, n_out, ops = _acc_plan(frag.root.aggs,
                                            force_count=grouped)
    comp = ExprCompiler(frag.binfo)
    try:
        pred_fns = [comp.compile(pr) for pr in frag.preds]
        val_fns = [comp.compile(a.arg) for a in frag.root.aggs
                   if a.op in ("sum", "avg", "any")]
    except UnsupportedExpr as ex:
        return _Analysis(reason=f"unsupported expression: {ex}")
    for name in sorted(comp.cols):
        if not _col_f32_safe(frag.binfo.cols[name]):
            return _Analysis(reason=(
                f"column {name!r} has no f32-exact encoding "
                "(int without dictionary/domain <= 2^24)"))
    out = _Analysis(plan_=plan_, cnt_slot=cnt_slot, n_out=n_out, ops=ops,
                    fills=_slot_fills(frag.root.aggs, comp.schema,
                                      cnt_slot),
                    pred_fns=pred_fns, val_fns=val_fns,
                    col_names=sorted(comp.cols),
                    param_names=sorted(comp.params))
    n_in = len(out.col_names) + 1  # + validity/mask weight column
    if grouped:
        try:
            child_info = L.static_info(frag.root.child, catalog)
            out.strides, out.domain = L._group_layout(frag.root,
                                                      child_info)
        except (TypeError, ValueError) as ex:
            return _Analysis(reason=f"no dense group layout: {ex}")
        if out.domain > SR_K.MAX_GROUPS:
            return _Analysis(reason=(f"group domain {out.domain} > "
                                     f"MAX_GROUPS {SR_K.MAX_GROUPS}"))
        out.key_doms = [child_info.cols[k].group_domain
                        for k in frag.root.keys]
        out.block_default = R.choose_block_rows(n_in + 1, n_out,
                                                out.domain)
        if out.block_default is None:
            return _Analysis(reason="group accumulator exceeds VMEM budget")
    else:
        out.block_default = R.choose_block_rows(n_in, n_out)
        if out.block_default is None:
            return _Analysis(reason="input blocks exceed VMEM budget")
    return out


def _eligibility(frag: R.Fragment, catalog: P.Catalog) -> Tuple[bool, str]:
    a = _analyze(frag, catalog)
    return (a.reason is None), (a.reason or "ok")


# ---------------------------------------------------------------------------
# emitters
# ---------------------------------------------------------------------------


def _assign_grouped_outputs(out_cols: Dict[str, Any],
                            aggs: Tuple[P.AggSpec, ...], plan_: Any,
                            out: Any, cnt: Any,
                            out_info: L.StaticInfo) -> None:
    """Map the [n_out, G] kernel accumulator rows onto output columns
    (shared by the grouped and join-probe emitters): sums verbatim, avg
    recomposed from sum/count, count from the shared count slot, any_
    cast back to its static output dtype (the kernel runs f32)."""
    for a, (kind, slot) in zip(aggs, plan_):
        if kind == "sum":
            out_cols[a.name] = out[slot]
        elif kind == "avg":
            out_cols[a.name] = out[slot] / jnp.maximum(cnt, 1.0)
        elif kind == "any":
            dt = L._JNP_OF[out_info.cols[a.name].dtype]
            out_cols[a.name] = out[slot].astype(dt)
        else:
            out_cols[a.name] = cnt.astype(jnp.int32)


def _emit(frag: R.Fragment, catalog: P.Catalog, grouped: bool) -> R.Emitter:
    """Build the trace-time emitter for a matched fragment.

    Everything static happened at dispatch time in :func:`_analyze`
    (shared with eligibility): expressions compiled to closures over
    kernel blocks, dictionaries resolved to code tests, accumulator
    layout and block shape fixed.  The returned emitter only does the
    traced work: pad/reshape the boundary columns, pack the param
    vector, call the kernel, assemble the output stream."""
    aggs = frag.root.aggs
    ana = _analyze(frag, catalog)
    assert ana.reason is None, ana.reason  # eligibility checked it
    plan_, cnt_slot, n_out = ana.plan_, ana.cnt_slot, ana.n_out
    ops, fills = ana.ops, ana.fills
    pred_fns, val_fns = ana.pred_fns, ana.val_fns
    col_names, param_names = ana.col_names, ana.param_names
    strides, domain, key_doms = ana.strides, ana.domain, ana.key_doms
    block_default = ana.block_default
    out_info = L.static_info(frag.root, catalog)

    def value_fn(scal_ref, blocks, code_block=None):
        cols = dict(zip(col_names, blocks))
        scal = {name: scal_ref[i] for i, name in enumerate(param_names)}
        # weight = validity (mask + padding) AND the compiled predicate
        pred = _as_bool(blocks[len(col_names)])
        for fn in pred_fns:
            pred = pred & _as_bool(fn(cols, scal))
        w = pred.astype(jnp.float32)
        # where, NOT multiply-by-weight: excluded/padding rows can hold
        # values whose expressions go inf/nan (division on zero-filled
        # shard padding), and nan * 0 would poison the accumulator.
        # "max" (any_) slots carry their neutral fill instead of 0.
        outs = [jnp.where(pred, fn(cols, scal),
                          jnp.float32(fills[j])).astype(jnp.float32)
                for j, fn in enumerate(val_fns)]
        if cnt_slot is not None:
            outs.append(w)
        return outs

    def run(bstream: L.Stream, params: Optional[Dict[str, Any]],
            interpret: bool) -> L.Stream:
        n = bstream.n

        def _param(name):
            if params is None or name not in params:
                raise KeyError(
                    f"unbound query parameter {name!r}; pass a binding, "
                    f"e.g. lowered.compile()({name}=...)")
            return jnp.asarray(params[name]).astype(jnp.float32)

        scal = (jnp.stack([_param(p) for p in param_names])
                if param_names else jnp.zeros((1,), jnp.float32))
        block_rows = min(block_default, max(1, n // LANES))
        blocks = [FA_OPS.pad_reshape(bstream.cols[c].astype(jnp.float32),
                                     block_rows, 0.0)
                  for c in col_names]
        # validity column: real rows carry the stream mask (all-ones when
        # unmasked); padding rows carry 0 so they never contribute.  A
        # Scan boundary is maskless when matched, but under the sharded
        # ``parallel`` engine the SAME fragment re-lowers per shard with
        # a padding mask on the spine scan -- so always honor the stream
        # mask, not just the dispatch-time ``masked`` flag.
        valid = bstream.the_mask().astype(jnp.float32)
        blocks.append(FA_OPS.pad_reshape(valid, block_rows, 0.0))

        out_cols: Dict[str, jnp.ndarray] = {}
        if grouped:
            code = jnp.zeros((n,), jnp.int32)
            for ke, s in zip(frag.key_exprs, strides):
                kv = L.eval_expr(ke, bstream, params)
                code = code + kv.astype(jnp.int32) * np.int32(s)
            codes = FA_OPS.pad_reshape(code, block_rows, 0)
            out = SR_K.segmented_multi_sum(
                value_fn, blocks, codes, scal, n_out, domain, block_rows,
                interpret, ops=ops, fills=fills)
            cnt = out[cnt_slot]
            gidx = jnp.arange(domain, dtype=jnp.int32)
            for k, s, dk in zip(frag.root.keys, strides, key_doms):
                out_cols[k] = (gidx // np.int32(s)) % np.int32(dk)
            _assign_grouped_outputs(out_cols, aggs, plan_, out, cnt,
                                    out_info)
            return L.Stream(out_cols, cnt > 0, out_info)

        outs = FA_K.filter_agg_general(value_fn, blocks, scal, n_out,
                                       block_rows, interpret)
        sums = [jnp.sum(o) for o in outs]
        cnt = sums[cnt_slot] if cnt_slot is not None else None
        for a, (kind, slot) in zip(aggs, plan_):
            if kind == "sum":
                out_cols[a.name] = sums[slot][None]
            elif kind == "avg":
                out_cols[a.name] = (sums[slot] / jnp.maximum(cnt, 1.0))[None]
            else:
                out_cols[a.name] = cnt.astype(jnp.int32)[None]
        return L.Stream(out_cols, None, out_info)

    return run


def _emit_scalar(frag, catalog):
    return _emit(frag, catalog, grouped=False)


def _emit_grouped(frag, catalog):
    return _emit(frag, catalog, grouped=True)


def _emit_masked(frag, catalog):
    # "streaming into either": the mask is just another weight column,
    # so the keyed/keyless emitters apply unchanged
    return _emit(frag, catalog, grouped=bool(frag.root.keys))


# ---------------------------------------------------------------------------
# the join-probe pattern: fused probe + gather + filter + aggregate
# ---------------------------------------------------------------------------


def _match_join_probe(node, catalog, frag=_UNSET):
    """Aggregate whose boundary is an inner N:1 join served by the
    cached build-side index (DESIGN.md section 10): the binary-search
    probe, payload gather, residual predicate and partial aggregate all
    fuse into one Pallas pass over the probe stream."""
    if frag is _UNSET:
        frag = match_fragment(node, catalog)
    if frag is None or not isinstance(frag.boundary, P.Join):
        return None
    if frag.boundary.how != "inner":
        return None
    spec, _ = L.resolve_build_index(frag.boundary, catalog)
    if spec is None:
        return None
    return frag


@dataclasses.dataclass
class _ProbeAnalysis:
    """Static layout of a join-probe fragment (memoized on
    ``Fragment.probe_analysis``): the probe/build column split on top of
    everything the shared aggregate analysis computes."""

    reason: Optional[str] = None  # None = eligible
    spec: Any = None              # L.JoinIndexSpec of the boundary join
    plan_: Any = None
    cnt_slot: Optional[int] = None
    n_out: int = 0
    ops: Tuple[str, ...] = ()
    fills: Tuple[float, ...] = ()
    pred_fns: Any = None
    val_fns: Any = None
    key_fns: Any = None           # compiled group-key closures
    probe_cols: Any = None        # streamed probe-side columns
    build_cols: Any = None        # gathered build-payload columns
    param_names: Any = None
    strides: Any = None
    domain: Optional[int] = None
    key_doms: Any = None
    accum: Optional[str] = None   # "onehot" | "scatter" | None (keyless)
    block_default: Optional[int] = None
    slab_rows: Optional[int] = None  # paged build side; None = resident


_SLAB_ROWS_DEFAULT = 512  # [slab_rows, 128] build page; halved until it fits


def _choose_slab(n_build: int, brows: int, n_in: int, n_out: int,
                 num_groups: Optional[int] = None, acc_bytes: int = 0
                 ) -> Tuple[Optional[int], Optional[int]]:
    """Largest build-side slab (halving from :data:`_SLAB_ROWS_DEFAULT`,
    floor 1) whose double-buffered HBM->VMEM page plus probe blocks and
    ``acc_bytes`` of accumulator fits the VMEM budget.  Returns
    ``(slab_rows, block_rows)`` or ``(None, None)`` if even a one-row
    slab spills."""
    slab = min(_SLAB_ROWS_DEFAULT, max(1, brows // 2))
    while slab >= 1:
        paged = n_build * slab * LANES * 4 * 2  # x2: Pallas double-buffers
        bd = R.choose_block_rows(n_in, n_out, num_groups,
                                 resident_bytes=paged + acc_bytes)
        if bd is not None:
            return slab, bd
        slab //= 2
    return None, None


def _analyze_probe(frag: R.Fragment, catalog: P.Catalog) -> _ProbeAnalysis:
    if frag.probe_analysis is not None:
        return frag.probe_analysis
    frag.probe_analysis = out = _analyze_probe_uncached(frag, catalog)
    return out


def _analyze_probe_uncached(frag: R.Fragment,
                            catalog: P.Catalog) -> _ProbeAnalysis:
    join = frag.boundary
    spec, reason = L.resolve_build_index(join, catalog)
    if spec is None:  # matcher checked; kept for direct eligibility calls
        return _ProbeAnalysis(reason=reason)
    grouped = bool(frag.root.keys)
    supported = _SUPPORTED_GROUPED_AGGS if grouped else _SUPPORTED_AGGS
    bad = sorted({a.op for a in frag.root.aggs if a.op not in supported})
    if bad:
        return _ProbeAnalysis(reason=f"unsupported aggregate op(s) {bad}")
    if frag.binfo.n_rows <= 0:
        return _ProbeAnalysis(reason="empty probe stream")
    # the combined join key streams through the kernel as f32: its
    # domain must stay exactly representable
    combined = 1
    for d in spec.doms:
        combined *= d
    if combined > F32_EXACT:
        return _ProbeAnalysis(reason=(
            f"combined join-key domain {combined} has no f32-exact "
            "encoding (> 2^24)"))
    plan_, cnt_slot, n_out, ops = _acc_plan(frag.root.aggs,
                                            force_count=grouped)
    comp = ExprCompiler(frag.binfo)
    try:
        pred_fns = [comp.compile(pr) for pr in frag.preds]
        val_fns = [comp.compile(a.arg) for a in frag.root.aggs
                   if a.op in ("sum", "avg", "any")]
        key_fns = [comp.compile(ke) for ke in frag.key_exprs]
    except UnsupportedExpr as ex:
        return _ProbeAnalysis(reason=f"unsupported expression: {ex}")
    for name in sorted(comp.cols):
        if not _col_f32_safe(frag.binfo.cols[name]):
            return _ProbeAnalysis(reason=(
                f"column {name!r} has no f32-exact encoding "
                "(int without dictionary/domain <= 2^24)"))
    lnames = set(join.left.schema(catalog).names)
    probe_cols = sorted((set(comp.cols) & lnames) | set(join.left_on))
    build_cols = sorted(set(comp.cols) - lnames)
    out = _ProbeAnalysis(
        spec=spec, plan_=plan_, cnt_slot=cnt_slot, n_out=n_out, ops=ops,
        fills=_slot_fills(frag.root.aggs, comp.schema, cnt_slot),
        pred_fns=pred_fns, val_fns=val_fns, key_fns=key_fns,
        probe_cols=probe_cols, build_cols=build_cols,
        param_names=sorted(comp.params))
    # build-side arrays (sorted keys [+ mask] + payload) stay VMEM-
    # resident across the whole grid
    b_rows = catalog.table(spec.table).num_rows
    b_pad = -(-b_rows // LANES) * LANES
    n_build = 1 + (1 if spec.masked else 0) + len(build_cols)
    resident = n_build * b_pad * 4
    n_in = len(probe_cols) + 1  # + validity column
    if not grouped:
        out.block_default = R.choose_block_rows(n_in, n_out,
                                                resident_bytes=resident)
        if out.block_default is None:
            # whole-build residency spills VMEM: switch to the tiled
            # variant that pages the build side HBM->VMEM in slabs
            out.slab_rows, out.block_default = _choose_slab(
                n_build, b_pad // LANES, n_in, n_out)
            if out.block_default is None:
                return _ProbeAnalysis(reason=(
                    "input blocks exceed VMEM budget even with a "
                    "paged build side"))
        return out
    try:
        child_info = L.static_info(frag.root.child, catalog)
        out.strides, out.domain = L._group_layout(frag.root, child_info)
    except (TypeError, ValueError) as ex:
        return _ProbeAnalysis(reason=f"no dense group layout: {ex}")
    out.key_doms = [child_info.cols[k].group_domain
                    for k in frag.root.keys]
    if out.domain <= SR_K.MAX_GROUPS:
        out.accum = "onehot"
        out.block_default = R.choose_block_rows(
            n_in, n_out, out.domain, resident_bytes=resident)
        if out.block_default is not None:
            return out
        out.slab_rows, out.block_default = _choose_slab(
            n_build, b_pad // LANES, n_in, n_out, out.domain)
        if out.block_default is not None:
            return out
        out.slab_rows = None
        # the dense accumulator spills VMEM: fall through to scatter
    if out.domain > JP_K.SCATTER_MAX_GROUPS:
        return _ProbeAnalysis(reason=(
            f"group domain {out.domain} > SCATTER_MAX_GROUPS "
            f"{JP_K.SCATTER_MAX_GROUPS}"))
    out.accum = "scatter"
    acc_bytes = n_out * out.domain * 4 * 2 + resident
    out.block_default = R.choose_block_rows(n_in, n_out,
                                            resident_bytes=acc_bytes)
    if out.block_default is None:
        out.slab_rows, out.block_default = _choose_slab(
            n_build, b_pad // LANES, n_in, n_out,
            acc_bytes=n_out * out.domain * 4 * 2)
        if out.block_default is None:
            return _ProbeAnalysis(reason="accumulator exceeds VMEM budget")
    return out


def _probe_eligibility(frag: R.Fragment,
                       catalog: P.Catalog) -> Tuple[bool, str]:
    a = _analyze_probe(frag, catalog)
    return (a.reason is None), (a.reason or "ok")


def _emit_join_probe(frag: R.Fragment, catalog: P.Catalog):
    """Build the join-probe lowering hook.

    Unlike the boundary-stream emitters this is a *custom-lowering*
    emitter (``KernelPattern.custom_lower``): it lowers the probe and
    build sides itself and pulls the cached index streams from the
    ``scans`` environment that ``lower.build_callable`` populates."""
    ana = _analyze_probe(frag, catalog)
    assert ana.reason is None, ana.reason  # eligibility checked it
    join = frag.boundary
    aggs = frag.root.aggs
    grouped = bool(frag.root.keys)
    spec = ana.spec
    (plan_, cnt_slot, n_out, ops, fills, pred_fns, val_fns, key_fns,
     probe_cols, build_cols, param_names, strides, domain, key_doms,
     accum, block_default, slab_rows) = (
        ana.plan_, ana.cnt_slot, ana.n_out, ana.ops, ana.fills,
        ana.pred_fns, ana.val_fns, ana.key_fns, ana.probe_cols,
        ana.build_cols, ana.param_names, ana.strides, ana.domain,
        ana.key_doms, ana.accum, ana.block_default, ana.slab_rows)
    out_info = L.static_info(frag.root, catalog)
    left_on, doms = join.left_on, spec.doms
    masked_build = spec.masked

    def body_fn(scal_ref, pblocks, barrays):
        cols = dict(zip(probe_cols, pblocks))
        valid = _as_bool(pblocks[len(probe_cols)])
        scal = {name: scal_ref[i] for i, name in enumerate(param_names)}
        # combined probe key (f32-exact: domain checked at dispatch)
        kp = cols[left_on[0]]
        for k, d in zip(left_on[1:], doms[1:]):
            kp = kp * float(d) + cols[k]
        kb_flat = barrays[0].reshape(-1)
        idx, hit = JP_K.probe_sorted(kb_flat, kp)
        matched = hit & valid
        ai = 1
        if masked_build:
            # post-probe mask validation: keys are unique, so checking
            # the matched row's filter mask is exact
            matched = matched & (jnp.take(barrays[ai].reshape(-1), idx,
                                          mode="clip") > 0.5)
            ai += 1
        for name in build_cols:
            cols[name] = jnp.take(barrays[ai].reshape(-1), idx,
                                  mode="clip")
            ai += 1
        pred = matched
        for fn in pred_fns:
            pred = pred & _as_bool(fn(cols, scal))
        w = pred.astype(jnp.float32)
        outs = [jnp.where(pred, fn(cols, scal),
                          jnp.float32(fills[j])).astype(jnp.float32)
                for j, fn in enumerate(val_fns)]
        if cnt_slot is not None:
            outs.append(w)
        codes = None
        if grouped:
            code = jnp.zeros_like(kp)
            for kf, s in zip(key_fns, strides):
                code = code + kf(cols, scal) * float(s)
            codes = jnp.where(pred, code, 0.0).astype(jnp.int32)
        return outs, codes

    def run(catalog_, scans, params, interpret) -> L.Stream:
        left = L.lower_node(join.left, catalog_, scans, params)
        right = L.lower_node(join.right, catalog_, scans, params)
        jidx = scans.get(L.index_stream_key(join))
        if jidx is None:
            raise RuntimeError(
                "join-probe fragment lowered without its cached index "
                "stream; the engine must run lower.build_callable")
        perm, keys, _ = jidx  # the kernel searches the sorted keys

        def _param(name):
            if params is None or name not in params:
                raise KeyError(
                    f"unbound query parameter {name!r}; pass a binding, "
                    f"e.g. lowered.compile()({name}=...)")
            return jnp.asarray(params[name]).astype(jnp.float32)

        scal = (jnp.stack([_param(p_) for p_ in param_names])
                if param_names else jnp.zeros((1,), jnp.float32))
        n = left.n
        block_rows = min(block_default, max(1, n // LANES))
        pblocks = [FA_OPS.pad_reshape(left.cols[c].astype(jnp.float32),
                                      block_rows, 0.0)
                   for c in probe_cols]
        pblocks.append(FA_OPS.pad_reshape(
            left.the_mask().astype(jnp.float32), block_rows, 0.0))
        # build arrays ride in sorted by the cached permutation, so the
        # in-kernel probe position indexes them directly
        barrays = [JP_K.pad_build(keys.astype(jnp.float32), jnp.inf,
                                  slab_rows=slab_rows)]
        if masked_build:
            barrays.append(JP_K.pad_build(
                right.the_mask().astype(jnp.float32)[perm], 0.0,
                slab_rows=slab_rows))
        for name in build_cols:
            barrays.append(JP_K.pad_build(
                right.cols[name].astype(jnp.float32)[perm], 0.0,
                slab_rows=slab_rows))

        out_cols: Dict[str, jnp.ndarray] = {}
        if grouped:
            out = JP_K.join_probe_agg(
                body_fn, pblocks, barrays, scal, n_out, block_rows,
                num_groups=domain, ops=ops, fills=fills, accum=accum,
                slab_rows=slab_rows, interpret=interpret)
            cnt = out[cnt_slot]
            gidx = jnp.arange(domain, dtype=jnp.int32)
            for k, s, dk in zip(frag.root.keys, strides, key_doms):
                out_cols[k] = (gidx // np.int32(s)) % np.int32(dk)
            _assign_grouped_outputs(out_cols, aggs, plan_, out, cnt,
                                    out_info)
            return L.Stream(out_cols, cnt > 0, out_info)

        outs = JP_K.join_probe_agg(body_fn, pblocks, barrays, scal,
                                   n_out, block_rows, slab_rows=slab_rows,
                                   interpret=interpret)
        sums = [jnp.sum(o) for o in outs]
        cnt = sums[cnt_slot] if cnt_slot is not None else None
        for a, (kind, slot) in zip(aggs, plan_):
            if kind == "sum":
                out_cols[a.name] = sums[slot][None]
            elif kind == "avg":
                out_cols[a.name] = (sums[slot]
                                    / jnp.maximum(cnt, 1.0))[None]
            else:
                out_cols[a.name] = cnt.astype(jnp.int32)[None]
        return L.Stream(out_cols, None, out_info)

    return run


R.register_pattern(R.KernelPattern(
    name="filter-scalar-agg", matcher=_match_scalar,
    eligibility=_eligibility, emitter=_emit_scalar))
R.register_pattern(R.KernelPattern(
    name="grouped-agg", matcher=_match_grouped,
    eligibility=_eligibility, emitter=_emit_grouped))
# join-probe outranks masked-filter-project: where both match (an inner
# index-served join under the aggregate), fusing the probe wins
R.register_pattern(R.KernelPattern(
    name="join-probe", matcher=_match_join_probe,
    eligibility=_probe_eligibility, emitter=_emit_join_probe,
    requires_index=True, custom_lower=True,
    pallas_refusal=("the in-kernel binary-search probe and payload "
                    "gathers are data-dependent gathers Mosaic cannot "
                    "lower (interpret mode only)")))
R.register_pattern(R.KernelPattern(
    name="masked-filter-project", matcher=_match_masked,
    eligibility=_eligibility, emitter=_emit_masked))
