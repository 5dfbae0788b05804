"""The kernel-pattern registry: plan fragments -> Pallas kernels.

The paper's headline claim (sections 1, 4.1) is that Flare generates
*specialized native operators* for hot plan fragments instead of stitching
generic library calls.  Our ``compiled`` engine fuses the whole plan into
one XLA program, but every operator lowers to generic ``jnp`` ops; this
registry is where hand-scheduled Pallas kernels plug in.

A :class:`KernelPattern` is (HiFrames-style) a *matcher* over
:class:`repro.core.plan.Plan` fragments plus an *emitter* that replaces
the fragment's generic lowering with a kernel call, guarded by an
*eligibility* predicate (supported aggregate ops / expression forms,
f32-exactness of the streamed columns, backend + interpret-mode support,
and a VMEM budget check for the chosen block shape).  The dispatch pass
(``repro.native.dispatch``) runs the registry over the optimized plan and
records every decision in a :class:`DispatchReport` -- which patterns
fired, which fell back, and why -- surfaced on
``CompileStats.dispatch``.

Future kernels (join probe, sort, top-k) land here as new
``register_pattern`` entries instead of engine forks.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.core import expr as E
from repro.core import lower as L
from repro.core import plan as P

LANES = 128

#: Conservative per-core VMEM budget for kernel working sets: ~16 MiB
#: physical, kept at 12 MiB to leave room for double buffering.
VMEM_BUDGET_BYTES = 12 * (1 << 20)

#: Emitter signature: (boundary stream, param env, interpret) -> output
#: stream of the fragment root.  Built at dispatch time, called at trace
#: time inside the whole-query program.
Emitter = Callable[[L.Stream, Optional[Dict[str, Any]], bool], L.Stream]


@dataclasses.dataclass
class Fragment:
    """A matched plan fragment: an Aggregate root plus its Filter/Project
    prologue, rebased onto the *boundary* node whose stream the kernel
    consumes.  All expressions are substituted into boundary-column
    terms, so the emitter can compile them straight into the kernel body.
    """

    root: P.Aggregate
    boundary: P.Plan
    preds: Tuple[E.Expr, ...]                 # prologue filter conjuncts
    agg_args: Tuple[Optional[E.Expr], ...]    # per AggSpec (None = count)
    key_exprs: Tuple[E.Expr, ...]             # group keys, boundary terms
    masked: bool                              # boundary may carry a mask
    binfo: L.StaticInfo                       # boundary static info
    # memo slot: the expression-compilation/layout analysis shared by
    # eligibility and emitter (patterns._analyze) -- computed once
    analysis: Any = dataclasses.field(default=None, repr=False)
    # separate memo for the join-probe pattern (patterns._analyze_probe):
    # its layout differs (probe/build column split, in-kernel probe), so
    # it must not collide with the shared aggregate analysis above
    probe_analysis: Any = dataclasses.field(default=None, repr=False)


@dataclasses.dataclass
class KernelPattern:
    """A registry entry: name + matcher + eligibility + emitter factory.

    ``matcher(node, catalog)`` returns a :class:`Fragment` or None;
    ``eligibility(fragment, catalog)`` returns ``(ok, reason)``;
    ``emitter(fragment, catalog)`` builds the trace-time
    :data:`Emitter`.  ``pallas_refusal`` says why a pattern whose
    kernel only runs in Pallas interpret mode cannot compile for a TPU
    (None: it compiles); dispatch records it as the fallback reason
    whenever it lowers for the chip.
    """

    name: str
    # matcher(node, catalog, frag=...): the dispatch pass pre-computes
    # the standard Aggregate fragment walk ONCE per node and passes it
    # as ``frag`` (possibly None = walk found no fragment) so sibling
    # patterns don't re-analyze; when ``frag`` is omitted the matcher
    # walks itself.  Custom matchers may ignore it entirely.
    matcher: Callable[..., Optional[Fragment]]
    eligibility: Callable[[Fragment, P.Catalog], Tuple[bool, str]]
    emitter: Callable[[Fragment, P.Catalog], Emitter]
    pallas_refusal: Optional[str] = None
    #: the pattern probes a cached build-side join index (``join-probe``):
    #: skipped entirely when lowering with ``join_index=False``
    requires_index: bool = False
    #: the emitter lowers its operand streams itself -- it is called as
    #: ``emitter(catalog, scans, params, interpret)`` (full custom-
    #: lowering context) instead of ``emitter(bstream, params,
    #: interpret)`` over one pre-lowered boundary stream
    custom_lower: bool = False


_REGISTRY: Dict[str, KernelPattern] = {}


def register_pattern(pattern: KernelPattern) -> KernelPattern:
    """Register ``pattern`` (last registration wins on name collision).
    Patterns are tried in registration order; first eligible match wins.
    """
    _REGISTRY[pattern.name] = pattern
    return pattern


def get_pattern(name: str) -> KernelPattern:
    return _REGISTRY[name]


def patterns() -> List[KernelPattern]:
    return list(_REGISTRY.values())


def available_patterns() -> List[str]:
    return list(_REGISTRY)


# ---------------------------------------------------------------------------
# VMEM budgeting
# ---------------------------------------------------------------------------


def vmem_estimate(n_cols: int, block_rows: int, n_out: int,
                  num_groups: Optional[int] = None,
                  resident_bytes: int = 0) -> int:
    """Bytes of VMEM the kernel's working set needs at ``block_rows``.

    Input blocks are double-buffered (x2).  The grouped variant keeps
    the step's ``n_out`` value blocks and one membership mask live
    across its group loop, plus the resident ``[G, n_out, 128]``
    accumulator (an output block, double-buffered; sublanes pad to 8).
    ``resident_bytes`` covers whole-array inputs pinned across grid
    steps (the join-probe kernel's build-side arrays)."""
    block = block_rows * LANES * 4
    total = n_cols * block * 2 + resident_bytes
    if num_groups is None:
        total += n_out * LANES * 4 * 2          # out + scratch rows
    else:
        total += (n_out + 1) * block
        total += num_groups * (-(-n_out // 8) * 8) * LANES * 4 * 2
    return total


def choose_block_rows(n_cols: int, n_out: int,
                      num_groups: Optional[int] = None,
                      default: int = 256,
                      resident_bytes: int = 0) -> Optional[int]:
    """Largest block_rows (halving from ``default``, floor 8) whose
    working set fits :data:`VMEM_BUDGET_BYTES`; None if even 8 spills."""
    block_rows = default
    while block_rows >= 8:
        if vmem_estimate(n_cols, block_rows, n_out, num_groups,
                         resident_bytes) <= VMEM_BUDGET_BYTES:
            return block_rows
        block_rows //= 2
    return None


# ---------------------------------------------------------------------------
# dispatch telemetry
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Decision:
    """One dispatch decision for one plan fragment."""

    pattern: str   # pattern name ("" when no pattern was eligible)
    node: str      # fragment root, human-readable (plan.describe())
    fired: bool
    mode: str      # "pallas" | "interpret" | "" (fallback)
    reason: str    # "ok" (+ patterns passed over) or why it fell back

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class DispatchReport:
    """Per-query dispatch report: which patterns fired, which fragments
    fell back to the generic jnp lowering, and why.  Attached to
    ``Lowered.dispatch_report`` / ``CompileStats.dispatch``.

    ``index_decisions`` is the join-index section (DESIGN.md sec. 10):
    one entry per join, saying whether its build side probes the cached
    base-table index (``fired``; ``mode`` "direct" for the slot-table
    probe of a dense key domain, "sorted" for the binary search) or
    rebuilds the sorted keys in-program, and why -- recorded for ANY
    compiled/parallel template with joins, native or not.
    """

    decisions: List[Decision] = dataclasses.field(default_factory=list)
    index_decisions: List[Decision] = dataclasses.field(
        default_factory=list)

    def add(self, d: Decision) -> None:
        self.decisions.append(d)

    @property
    def fired(self) -> List[Decision]:
        return [d for d in self.decisions if d.fired]

    @property
    def fallbacks(self) -> List[Decision]:
        return [d for d in self.decisions if not d.fired]

    def fired_patterns(self) -> List[str]:
        return [d.pattern for d in self.fired]

    @property
    def joins_cached(self) -> List[Decision]:
        """Joins whose build side probes the cached index."""
        return [d for d in self.index_decisions if d.fired]

    @property
    def joins_rebuilt(self) -> List[Decision]:
        """Joins that re-sort their build keys inside the program."""
        return [d for d in self.index_decisions if not d.fired]

    def to_dict(self) -> Dict[str, Any]:
        return {"fired": [d.to_dict() for d in self.fired],
                "fallbacks": [d.to_dict() for d in self.fallbacks],
                "joins_cached": [d.to_dict() for d in self.joins_cached],
                "joins_rebuilt": [d.to_dict() for d in self.joins_rebuilt]}

    def __str__(self) -> str:
        if not self.decisions and not self.index_decisions:
            return "native dispatch: no dispatchable fragments"
        lines = ["native dispatch:"] if self.decisions else []
        for d in self.decisions:
            if d.fired:
                lines.append(f"  + {d.node} -> {d.pattern} [{d.mode}]"
                             + (f" ({d.reason[4:]})" if d.reason != "ok"
                                else ""))
            else:
                lines.append(f"  - {d.node} -> jnp fallback ({d.reason})")
        if self.index_decisions:
            lines.append("join index cache:")
            for d in self.index_decisions:
                if d.fired:
                    lines.append(f"  + {d.node} -> cached index "
                                 f"({d.mode} probe)")
                else:
                    lines.append(f"  - {d.node} -> in-program argsort "
                                 f"({d.reason})")
        return "\n".join(lines)
