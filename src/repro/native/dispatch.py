"""The dispatch pass: annotate matched fragments, hook into lowering.

Runs AFTER the optimizer (``repro.core.stages.lower_plan`` with
``native=True`` or the ``compiled-native`` engine alias): every
dispatchable fragment is wrapped in a :class:`NativeOp` annotation node
carrying the pattern's pre-built emitter; everything else keeps its
generic jnp lowering.  ``NativeOp`` implements the custom-lowering
protocol of ``repro.core.lower`` (``lower_stream`` /
``static_info_hook`` / ``required_columns_hook``), so
``lower.build_callable`` traces the kernel call into the SAME
whole-query XLA program as the surrounding operators.

Off-TPU the emitters run the Pallas kernels in interpret mode
(recorded as the decision's ``mode``); on a TPU they compile through
Mosaic (mode ``pallas``), and a pattern that declares a
``pallas_refusal`` falls back with that reason.

Composition with the sharded ``parallel`` engine: its shard planner
(``repro.core.parallel.shard_plan``) calls :func:`rewrite_plan` on the
shard-planned plan, AFTER rewriting merge-point aggregates into their
partial (avg -> sum+count) form -- so the pattern that fires is the one
each shard actually computes, the ``transform`` pass re-wraps the
``ShardMerge`` child automatically, and the kernel runs once per shard
inside the SPMD program (the per-shard report is
``repro.core.parallel.ShardedDispatchReport``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

from repro.core import lower as L
from repro.core import plan as P
from repro.core import stages as S
from repro.kernels import should_interpret
from repro.native import patterns as PAT
from repro.native import registry as R
from repro.obs import export as OX
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.resilience import faults as FZ


@dataclasses.dataclass(eq=False)
class NativeOp(P.Plan):
    """Annotation node: ``child`` (the matched fragment root, subtree
    intact) lowers through ``emitter`` onto a Pallas kernel instead of
    the generic jnp path.  Transparent for schema/static-info/column
    analysis; opaque (and pattern-tagged) for fingerprints, so native
    templates never share a compile-cache entry with plain compiled
    ones.

    ``custom_lower`` marks patterns (the ``join-probe`` kernel) whose
    emitter lowers the fragment's operand streams itself -- it is called
    with the full custom-lowering context ``(catalog, scans, params,
    interpret)`` instead of one pre-lowered boundary stream, because it
    needs the probe and build sides separately plus the cached index
    streams that ride in ``scans``.
    """

    child: P.Plan
    pattern: str
    emitter: R.Emitter
    interpret: bool
    custom_lower: bool = False

    def children(self) -> Tuple[P.Plan, ...]:
        return (self.child,)

    def with_children(self, kids):
        return NativeOp(kids[0], self.pattern, self.emitter, self.interpret,
                        self.custom_lower)

    def infer_schema(self, catalog):
        return self.child.schema(catalog)

    def describe(self):
        mode = "interpret" if self.interpret else "pallas"
        return f"NativeKernel[{self.pattern}/{mode}]"

    def fingerprint(self):
        mode = "interpret" if self.interpret else "pallas"
        return f"native[{self.pattern}:{mode}]({self.child.fingerprint()})"

    # -- repro.core.lower custom-lowering protocol ---------------------------

    def static_info_hook(self, catalog) -> L.StaticInfo:
        return L.static_info(self.child, catalog)

    def required_columns_hook(self, rec, needed) -> None:
        rec(self.child, needed)

    def lower_stream(self, catalog, scans, params) -> L.Stream:
        # trust boundary: a kernel emitter can refuse the geometry
        # (KernelBudgetError) -- injected here so the degradation
        # ladder sees the failure exactly where a real one surfaces
        FZ.fault_point("native.kernel", pattern=self.pattern)
        # named scope at trace time: the Pallas kernel's ops carry the
        # pattern name into the compiled program / device profiles
        with OX.kernel_scope(f"flare:{self.pattern}"):
            if self.custom_lower:
                return self.emitter(catalog, scans, params,
                                    self.interpret)
            boundary = PAT.boundary_of(self.child)
            bstream = L.lower_node(boundary, catalog, scans, params)
            return self.emitter(bstream, params, self.interpret)


def has_native_ops(p: P.Plan) -> bool:
    if isinstance(p, NativeOp):
        return True
    return any(has_native_ops(c) for c in p.children())


def rewrite_plan(p: P.Plan, catalog: P.Catalog,
                 interpret: Optional[bool] = None,
                 join_index: bool = True
                 ) -> Tuple[P.Plan, R.DispatchReport]:
    """Pattern-match the optimized plan bottom-up; wrap every eligible
    fragment in a :class:`NativeOp`.  Returns the annotated plan and the
    per-query :class:`repro.native.registry.DispatchReport` (which
    patterns fired, which fragments fell back, and why).

    ``join_index=False`` (the ``lower(join_index=False)`` escape hatch)
    skips patterns that require a cached build-side index (the
    ``join-probe`` kernel): without the index there is nothing for the
    kernel to binary-search."""
    if interpret is None:
        interpret = should_interpret()  # same policy as the kernel ops
    mode = "interpret" if interpret else "pallas"
    report = R.DispatchReport()
    OM.REGISTRY.inc("dispatch.rewrites")

    def rule(n: P.Plan) -> Optional[P.Plan]:
        if not isinstance(n, P.Aggregate):
            return None
        with OT.span("dispatch.match", node=n.describe()) as sp:
            reasons = []
            # one fragment walk per node, shared by the sibling matchers
            # (and, via Fragment.analysis, by eligibility + emitter)
            shared = PAT.match_fragment(n, catalog)
            for pat in R.patterns():
                if pat.requires_index and not join_index:
                    continue
                frag = pat.matcher(n, catalog, shared)
                if frag is None:
                    continue
                if not interpret and pat.pallas_refusal:
                    reasons.append(f"{pat.name}: {pat.pallas_refusal}")
                    continue
                ok, reason = pat.eligibility(frag, catalog)
                if not ok:
                    reasons.append(f"{pat.name}: {reason}")
                    continue
                emitter = pat.emitter(frag, catalog)
                # patterns passed over on the way stay on the record
                report.add(R.Decision(pattern=pat.name,
                                      node=n.describe(),
                                      fired=True, mode=mode,
                                      reason="; ".join(["ok"] + reasons)))
                OM.REGISTRY.inc("dispatch.fired")
                OM.REGISTRY.inc(f"dispatch.fired.{pat.name}")
                sp.set(fired=pat.name, mode=mode)
                return NativeOp(n, pat.name, emitter, interpret,
                                custom_lower=pat.custom_lower)
            why = "; ".join(reasons) if reasons else "no pattern matched"
            report.add(R.Decision(pattern="", node=n.describe(),
                                  fired=False, mode="", reason=why))
            OM.REGISTRY.inc("dispatch.fallback")
            for r in reasons:
                OM.REGISTRY.inc(
                    "dispatch.fallback." + r.split(":", 1)[0])
            sp.set(fired="", reason=why)
        return None

    with OT.span("dispatch", mode=mode) as dsp:
        out = P.transform(p, rule)
        dsp.set(fired=len(report.fired),
                fallbacks=len(report.fallbacks),
                patterns=",".join(report.fired_patterns()) or "none")
    # mark the root so NativeWholeQueryEngine.lower can tell "dispatch
    # ran, everything fell back" from "dispatch never ran" without
    # re-running the whole pass on all-fallback plans
    out._native_dispatched = True
    return out, report


# ---------------------------------------------------------------------------
# the "compiled-native" registry alias
# ---------------------------------------------------------------------------


class NativeWholeQueryEngine(S.WholeQueryEngine):
    """Whole-query compilation with native kernel dispatch.

    Registered as ``compiled-native`` so the Engine-protocol surface
    works standalone; ``stages.lower_plan`` normally annotates the plan
    (and captures the dispatch report) before this engine sees it, in
    which case ``lower`` is exactly the whole-query path."""

    name = "compiled-native"

    def lower(self, p: P.Plan, catalog: P.Catalog,
              param_specs) -> Any:
        if not getattr(p, "_native_dispatched", False):
            p, _ = rewrite_plan(p, catalog)
        return super().lower(p, catalog, param_specs)


S.register_engine(NativeWholeQueryEngine())
