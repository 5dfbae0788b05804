"""Shared benchmark plumbing: timing, CSV emission, JSON artifacts.

Every benchmark prints ``name,us_per_call,derived`` rows (the harness
contract).  ``derived`` carries the paper-facing quantity (a speedup
ratio, a loading time, a roofline term) as ``key=value`` pairs.

JSON perf artifacts go through :func:`write_report`: one code path for
every ``$BENCH_*_JSON`` env knob, and every artifact embeds the
process's :mod:`repro.obs` trace summary (per-phase counts + wall
time), so a ``FLARE_TRACE=1`` bench run ships its phase breakdown next
to its numbers.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional


class Timing(float):
    """A median-microseconds measurement that remembers how it was
    taken.  It IS the float the call sites do arithmetic on, plus:
    ``iters`` -- timed iterations actually run, ``cap_hit`` -- True
    when the ``max_iters`` cap cut a ``min_time_s``/``iters`` budget
    short, ``total_s`` -- summed timed wall clock."""

    iters: int
    cap_hit: bool
    total_s: float

    def __new__(cls, us: float, iters: int, cap_hit: bool,
                total_s: float) -> "Timing":
        self = super().__new__(cls, us)
        self.iters = iters
        self.cap_hit = cap_hit
        self.total_s = total_s
        return self


def time_call(fn: Callable, *, warmup: int = 1, iters: int = 5,
              min_time_s: float = 0.0, max_iters: int = 1000) -> Timing:
    """Median wall time per call, in microseconds (a :class:`Timing`).

    Runs at least ``iters`` timed calls and keeps going until
    ``min_time_s`` total timed seconds, hard-capped at ``max_iters``
    calls.  The cap used to be a silent ``i > 100`` break that
    truncated ``min_time_s`` runs without a trace; it is now explicit
    and *recorded*: ``Timing.cap_hit`` says the requested budget was
    cut short, and :func:`emit` surfaces ``iters``/``cap_hit`` on
    every row measured this way.
    """
    for _ in range(warmup):
        fn()
    times: List[float] = []
    t_total = 0.0
    i = 0
    cap_hit = False
    while i < iters or t_total < min_time_s:
        if i >= max_iters:  # budget not met, cap reached: say so
            cap_hit = True
            break
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        times.append(dt)
        t_total += dt
        i += 1
    times.sort()
    return Timing(times[len(times) // 2] * 1e6, i, cap_hit, t_total)


def entry(main: Callable[[], Any]) -> Any:
    """Run a benchmark's ``main`` as a program: with JAX's persistent
    compilation cache on (:mod:`repro.persist.xla_cache`)."""
    from repro.persist.xla_cache import enable_jax_compile_cache
    enable_jax_compile_cache()
    return main()


def emit(name: str, us: float, **derived) -> str:
    if isinstance(us, Timing):
        derived.setdefault("iters", us.iters)
        if us.cap_hit:
            derived.setdefault("cap_hit", 1)
    dtxt = ";".join(f"{k}={v}" for k, v in derived.items())
    line = f"{name},{float(us):.1f},{dtxt}"
    print(line, flush=True)
    return line


def trace_summary() -> Dict[str, Any]:
    """The process's tracer state + per-phase totals (embedded in every
    JSON perf artifact; all-zero when ``FLARE_TRACE`` is unset)."""
    from repro.obs import trace as OT
    summary = dict(OT.TRACER.stats())
    summary["phases"] = OT.Trace(OT.TRACER.spans()).phase_totals()
    return summary


def write_report(report: Dict[str, Any], env: str,
                 default: Optional[str] = None,
                 embed_trace: bool = True) -> Optional[str]:
    """Unified ``$BENCH_*_JSON`` artifact emission.

    ``env`` names the environment knob; ``default`` (when not None)
    makes the artifact unconditional with that fallback path, while
    ``default=None`` keeps the historical opt-in behaviour (no env var,
    no file).  The report lands with the :func:`trace_summary` attached
    under ``"trace"`` unless the caller already set one.  Returns the
    path written, or None.
    """
    path = os.environ.get(env) or default
    if not path:
        return None
    report = dict(report)
    if embed_trace:
        report.setdefault("trace", trace_summary())
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {path}")
    return path
