"""Graceful engine degradation: a fault costs latency, never a wrong
answer or availability.

When compilation or execution of a template fails with an error on the
closed *recoverable allowlist*, the template is re-lowered on the next
rung of the ladder::

    compiled-native -> compiled -> stage -> volcano
    parallel        -> compiled  (mesh/SPMD loss)

Each hop records a :class:`DegradeEvent` -- an obs counter
(``degrade.events`` + per-transition), a ``degrade`` trace span, and a
provenance entry on ``CompileStats.degraded`` -- so a degraded answer
is never silent.  The re-lower starts from the pre-rewrite plan the
front end handed to ``lower_plan`` (stashed as ``_degrade_src``), so
native annotation, shard planning and morsel wrapping are all redone
for the weaker rung rather than patched around.

The allowlist is deliberately closed (:func:`recoverable`):

* :class:`repro.kernels.KernelBudgetError` -- a Pallas kernel refused
  the geometry; the plain jnp lowering computes the same answer.
* persist ``StoreCorrupt`` / ``StoreVersionMiss`` -- a disk artifact
  is untrustworthy; recompiling from source is always correct.
* XLA compile failure (``jax.errors.JaxRuntimeError`` or the injected
  :class:`repro.resilience.faults.XlaCompileFault`) -- the interpreted
  rungs do not need XLA.
* :class:`repro.core.parallel.UnsupportedParallelPlan` -- the shard
  planner cannot express the plan; single-device compiled can.
* :class:`repro.resilience.faults.IndexBuildError` -- the join-index
  *infrastructure* failed; weaker rungs sort in-program.

Everything else -- ``MemoryBudgetError`` (the budget is a user
contract), ``UnindexableKeyError`` (a data property), binding
``TypeError``s, assertion failures, arithmetic errors -- still raises:
degradation may never mask a wrong-answer class of error.

Policy knob: ``FLARE_DEGRADE=off`` disables the ladder (faults raise
typed errors); ``auto`` (default) enables it.  The knob is read
per-failure, so tests can flip it without re-importing.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Optional, Tuple

from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.resilience.faults import IndexBuildError, XlaCompileFault

#: engine -> next (weaker) rung.  volcano is the floor: it interprets
#: the logical plan row-group-at-a-time with no XLA, no kernels, no
#: store and no mesh.
LADDER: Dict[str, str] = {
    "compiled-native": "compiled",
    "compiled": "stage",
    "stage": "volcano",
    "parallel": "compiled",
}


def enabled() -> bool:
    """``FLARE_DEGRADE=off`` disables the ladder; ``auto`` (default,
    any other value) enables it.  Read per-failure: failures are rare,
    so the env lookup costs nothing on the hot path."""
    return os.environ.get("FLARE_DEGRADE", "auto").lower() != "off"


def recoverable(err: BaseException) -> bool:
    """Membership in the closed allowlist of errors the ladder may
    absorb.  Anything else propagates typed."""
    if isinstance(err, (XlaCompileFault, IndexBuildError)):
        return True
    from repro.kernels import KernelBudgetError
    if isinstance(err, KernelBudgetError):
        return True
    from repro.persist.store import StoreCorrupt, StoreVersionMiss
    if isinstance(err, (StoreCorrupt, StoreVersionMiss)):
        return True
    try:
        from repro.core.parallel import UnsupportedParallelPlan
        if isinstance(err, UnsupportedParallelPlan):
            return True
    except ImportError:  # parallel engine never imported in this process
        pass
    # a real XLA compile/runtime failure surfaces as JaxRuntimeError
    import jax
    return isinstance(err, jax.errors.JaxRuntimeError)


@dataclasses.dataclass
class DegradeEvent:
    """One recorded hop down the ladder."""

    frm: str
    to: str
    phase: str            # "compile" | "execute"
    error_type: str
    message: str
    wall_time: float

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


_LOCK = threading.Lock()
_EVENTS: deque = deque(maxlen=256)


def events() -> Tuple[DegradeEvent, ...]:
    """Recent degradation events, oldest first (bounded ring)."""
    with _LOCK:
        return tuple(_EVENTS)


def clear_events() -> None:
    with _LOCK:
        _EVENTS.clear()


def _record(frm: str, to: str, phase: str,
            err: BaseException) -> DegradeEvent:
    ev = DegradeEvent(frm=frm, to=to, phase=phase,
                      error_type=type(err).__name__,
                      message=str(err)[:200], wall_time=time.time())
    with _LOCK:
        _EVENTS.append(ev)
    OM.REGISTRY.inc("degrade.events")
    OM.REGISTRY.inc(f"degrade.{frm}->{to}")
    OM.REGISTRY.inc(f"degrade.error.{ev.error_type}")
    with OT.span("degrade", frm=frm, to=to, phase=phase,
                 error=ev.error_type):
        pass
    return ev


def _rung_kwargs(src: Dict[str, Any], rung: str) -> Dict[str, Any]:
    """Re-lower kwargs for a weaker rung: native annotation and the
    mesh are shed (that is what degrading means), the morsel budget
    survives only onto the compiled rung (interpreted rungs stream via
    the row-group interpreter already), the join-index preference and
    caches carry over."""
    out_of_core = rung == "compiled"
    return dict(
        engine=rung,
        device_cache=src.get("device_cache"),
        compile_cache=src.get("compile_cache"),
        native=False,
        mesh=None,
        axis=src.get("axis", "data"),
        join_index=src.get("join_index", True),
        memory_budget=src.get("memory_budget") if out_of_core else None,
        morsel_rows=src.get("morsel_rows") if out_of_core else None,
    )


def next_lowered(src: Optional[Dict[str, Any]], frm: str,
                 err: BaseException, phase: str):
    """The fallback ``Lowered`` for a failure of engine ``frm``, or
    ``(None, None)`` when the ladder must not engage (policy off, error
    not on the allowlist, no re-lower source, or floor reached).

    Descends past rungs whose own re-lower fails recoverably; a
    non-recoverable re-lower failure abandons degradation so the
    caller re-raises the original error.
    """
    if src is None or not enabled() or not recoverable(err):
        return None, None
    from repro.core import stages as S
    rung = frm
    while True:
        nxt = LADDER.get(rung)
        if nxt is None:
            return None, None
        try:
            low = S.lower_plan(src["plan"], src["catalog"],
                               **_rung_kwargs(src, nxt))
        except Exception as relow_err:
            if recoverable(relow_err):
                rung = nxt
                continue
            return None, None
        return low, _record(frm, nxt, phase, err)


def stats() -> Dict[str, Any]:
    """Degradation telemetry for ``obs.snapshot()``."""
    evs = events()
    transitions: Dict[str, int] = {}
    for ev in evs:
        k = f"{ev.frm}->{ev.to}"
        transitions[k] = transitions.get(k, 0) + 1
    return {
        "enabled": enabled(),
        "events": len(evs),
        "transitions": transitions,
        "recent": [ev.to_dict() for ev in evs[-8:]],
    }
