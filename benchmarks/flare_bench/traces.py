"""From a ``jax.profiler`` trace to the device numbers of a run.

``load(dir)`` reads the newest ``.xplane.pb`` under a trace directory
into plain lists: the device's operations (``XLA Ops`` line of each
``/device:`` plane) and the host's annotations (the benchmark's own
``TraceAnnotation`` names, which start with ``bench:``).  ``reduce``
turns those lists into the numbers the per-layer metrics read; it is
pure Python, so a recorded trace checks it without a chip.

All times are in seconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Tuple

#: Host annotation opened just after tracing starts and closed just
#: before it stops: its span is the traced window.
WINDOW = "bench:traced"
PREFIX = "bench:"

Interval = Tuple[float, float]


def load(trace_dir: str) -> Dict[str, list]:
    """``{"device_ops": [[device, name, start, end], ...],
    "modules": [[device, name, start, end], ...],
    "host": [[name, start, end], ...]}`` of the newest trace: the
    device's operations, the programs (XLA modules) they belong to,
    and the benchmark's host annotations."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, modules, host = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {"XLA Ops": ops, "XLA Modules": modules}.get(
                    line.name)
                if into is not None:
                    for e in line.events:
                        # an op's event names its whole HLO instruction:
                        # keep the instruction's name ("%while.4")
                        into.append([plane.name, e.name.split(" = ", 1)[0],
                                     e.start_ns * 1e-9, e.end_ns * 1e-9])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append([e.name, e.start_ns * 1e-9,
                                     e.end_ns * 1e-9])
    return {"device_ops": ops, "modules": modules, "host": host}


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clock_offset(modules: List[list], spans: List[Tuple[str, float, float]],
                 reach: float = 0.02, step: float = 1e-4) -> float:
    """How far the device's clock reads ahead of the host's: the shift,
    within ``reach``, that puts the most programs wholly inside one host
    call, the smallest such shift on a tie.  A call waits for its
    programs, so each program runs inside the call that dispatched it;
    the two clocks differ by a millisecond or more in these traces,
    longer than a short query's call."""
    spans = sorted((ss, se) for _, ss, se in spans)
    if not modules or not spans:
        return 0.0
    starts = [ss for ss, _ in spans]
    best = (-1, 0.0, 0.0)
    n = int(round(reach / step))
    for k in sorted(range(-n, n + 1), key=abs):
        d = k * step
        inside = 0
        for _, _, s, e in modules:
            j = bisect.bisect_right(starts, s - d) - 1
            if j >= 0 and e - d <= spans[j][1]:
                inside += 1
        if inside > best[0]:
            best = (inside, -abs(d), d)
    return best[2]


def reduce(trace: Dict[str, list]) -> Optional[Dict[str, object]]:
    """The traced window, device busy time, the device time of each
    annotated query's programs with the number of calls that ran them,
    and a breakdown.  None when the trace holds no window annotation or
    no device operation.  Device times are moved onto the host's clock
    first (``clock_offset``)."""
    windows = [(s, e) for name, s, e in trace["host"] if name == WINDOW]
    if not windows or not trace["device_ops"]:
        return None
    lo, hi = windows[0]
    spans = [(name[len(PREFIX):], s, e) for name, s, e in trace["host"]
             if name != WINDOW and s >= lo and e <= hi]
    offset = clock_offset(trace.get("modules", []), spans)
    ops = [[d, n, s - offset, e - offset] for d, n, s, e in
           trace["device_ops"]]
    modules = [[d, n, s - offset, e - offset] for d, n, s, e in
               trace.get("modules", [])]
    devices = sorted({d for d, *_ in ops})
    per_dev = {d: union([(s, e) for dd, _, s, e in ops if dd == d])
               for d in devices}
    busy = [clip(per_dev[d], lo, hi) for d in devices]
    busy_s = sum(total(b) for b in busy) / len(devices)

    # device time of each query: the programs (XLA modules) whose middle
    # falls inside one of its host annotations
    device_s: Dict[str, float] = {}
    owners: Dict[str, set] = {}
    for d, name, s, e in modules:
        mid = 0.5 * (s + e)
        inside = [(se - ss, label, ss) for label, ss, se in spans
                  if ss <= mid <= se]
        if inside:
            _, label, start = min(inside)
            device_s[label] = device_s.get(label, 0.0) + (e - s) / len(
                devices)
            owners.setdefault(label, set()).add(start)
    calls = {label: len(starts) for label, starts in owners.items()}

    by_op: Dict[str, float] = {}
    for d, name, s, e in ops:
        for cs, ce in clip([(s, e)], lo, hi):
            by_op[name] = by_op.get(name, 0.0) + (ce - cs) / len(devices)
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]

    # idle gaps of the first device, named by the innermost benchmark
    # annotation open at the gap's middle
    gaps = []
    edges = [lo] + [x for s, e in busy[0] for x in (s, e)] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            mid = 0.5 * (s + e)
            open_ = [(ae - as_, name) for name, as_, ae in trace["host"]
                     if as_ <= mid <= ae and name != WINDOW]
            label = (min(open_)[1][len(PREFIX):] if open_ else "none")
            gaps.append((label, e - s))
    gaps.sort(key=lambda g: -g[1])

    # programs run inside the window, by module name without its id
    programs: Dict[str, List[float]] = {}
    for d, name, s, e in modules:
        if s >= lo and e <= hi:
            p = programs.setdefault(name.split("(")[0], [0, 0.0])
            p[0] += 1
            p[1] += e - s
    return {"window_s": hi - lo, "busy_s": busy_s, "clock_offset_s": offset,
            "device_s": device_s, "calls": calls, "programs": programs,
            "breakdown": {"device_ops": [[n, t] for n, t in top_ops],
                          "idle_gaps": [[n, t] for n, t in gaps[:10]]}}
