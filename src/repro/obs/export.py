"""Trace export: Chrome-trace-event JSON + device-profile annotations.

:func:`to_chrome` serialises spans into the Chrome trace event format
(``{"traceEvents": [...]}``, complete "X" duration events), which loads
directly in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``:

    FLARE_TRACE=1 PYTHONPATH=src python my_workload.py
    # then, at exit or any point:
    from repro import obs
    obs.dump_chrome("flare_trace.json")

Span attributes become the event ``args`` (with ``span_id``/
``parent_id`` preserved so tooling -- ``tools/trace_ci_check.py`` --
can rebuild the span tree from the JSON alone).

Device-side naming: every span is itself a profiler annotation while a
``jax.profiler`` session records (:mod:`repro.obs.trace`), and
:func:`kernel_scope` wraps an operator's lowering in
``jax.named_scope`` so the device operations it emits carry its name
("flare:join.probe", "flare:filter_scalar_agg") in the compiled
program's ``op_name`` metadata and the device profile's ``tf_op``.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
from typing import Any, Dict, Iterable, List, Optional

from repro.obs import trace as OT


def _json_safe(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, dict):
        return {str(k): _json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple, set)):
        return [_json_safe(x) for x in v]
    return str(v)


def to_chrome(spans: Optional[Iterable[OT.Span]] = None,
              process_name: str = "flare") -> Dict[str, Any]:
    """Chrome trace event dict for ``spans`` (default: the whole tracer
    buffer).  Timestamps are microseconds on the ``perf_counter``
    clock; every span becomes one complete ("X") duration event."""
    if spans is None:
        spans = OT.TRACER.spans()
    pid = os.getpid()
    events: List[Dict[str, Any]] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": process_name},
    }]
    for sp in spans:
        args = {str(k): _json_safe(v) for k, v in sp.attrs.items()}
        args["span_id"] = sp.span_id
        if sp.parent_id is not None:
            args["parent_id"] = sp.parent_id
        events.append({
            "name": sp.name,
            "ph": "X",
            "ts": sp.t0 * 1e6,
            "dur": max(0.0, sp.t1 - sp.t0) * 1e6,
            "pid": pid,
            "tid": sp.tid % (1 << 31),  # chrome wants a small-ish int
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def dump_chrome(path: str,
                spans: Optional[Iterable[OT.Span]] = None) -> str:
    """Write Chrome-trace JSON for ``spans`` (default: whole buffer)."""
    doc = to_chrome(spans)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def spans_from_chrome(doc: Dict[str, Any]) -> List[OT.Span]:
    """Rebuild :class:`repro.obs.trace.Span` objects (hence a
    :class:`repro.obs.trace.Trace` tree) from Chrome-trace JSON --
    the inverse of :func:`to_chrome`, used by the CI span gate and
    ``tools/flare_top.py`` on dumped traces."""
    out: List[OT.Span] = []
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        args = dict(ev.get("args", {}))
        span_id = args.pop("span_id", None)
        parent_id = args.pop("parent_id", None)
        sp = OT.Span(ev.get("name", "?"), span_id or 0, parent_id,
                     ev.get("tid", 0), args)
        sp.t0 = float(ev.get("ts", 0.0)) / 1e6
        sp.t1 = sp.t0 + float(ev.get("dur", 0.0)) / 1e6
        out.append(sp)
    return out


# ---------------------------------------------------------------------------
# device-profile naming hooks
# ---------------------------------------------------------------------------


def kernel_scope(name: str):
    """``jax.named_scope`` context manager (no-op fallback): applied at
    trace time around an operator's lowering (a native Pallas kernel or
    a generic operator's own work) so its ops carry the name into
    compiled programs and device profiles."""
    try:
        import jax
        return jax.named_scope(name)
    except Exception:
        return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# atexit dump: FLARE_TRACE_OUT=/path/to/trace.json
# ---------------------------------------------------------------------------

OUT_ENV = "FLARE_TRACE_OUT"
_atexit_registered = False
_atexit_lock = threading.Lock()


def install_atexit_dump(path: Optional[str] = None) -> Optional[str]:
    """Arrange for a Chrome-trace dump of the whole buffer at process
    exit.  Called automatically on package import when
    ``$FLARE_TRACE_OUT`` is set; idempotent."""
    global _atexit_registered
    path = path or os.environ.get(OUT_ENV)
    if not path:
        return None
    with _atexit_lock:
        if _atexit_registered:
            return path
        import atexit
        atexit.register(lambda: dump_chrome(path))
        _atexit_registered = True
    return path
