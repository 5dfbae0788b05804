"""The program's own spans and the named device scopes of its operators,
from the newest ``jax.profiler`` trace under ``.bench_traces/``.

While a profiler session records, every ``repro.obs`` span is a host
annotation named ``flare:<span>`` that carries the span's attributes as
stats, and the generic lowering names each operator's own device work
with a ``jax.named_scope`` (``flare:join.probe``, ``flare:join.gather``,
``flare:agg``, ``flare:filter``, ``flare:sort``), which the device
profile keeps as the ``tf_op`` stat of each operation's metadata.

``load(path)`` reads one ``.xplane.pb`` into plain lists: the ``flare:``
host spans with their stats, the benchmark's ``bench:`` annotations,
and each device's operations (``XLA Ops``, with the ``flare:`` scope of
their ``tf_op``) and programs (``XLA Modules``).  ``ProfileData`` does
not expose an event's metadata stats, so the device planes are decoded
from the protobuf wire format here.  The functions after it reduce
those lists to the per-layer metrics; they are pure Python, so a
hand-written trace checks them without a chip.

All times are in seconds; device times are moved onto the host's clock
by ``traces.clock_offset`` before they are compared with host spans.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

from benchmarks.flare_bench import traces

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACES = os.path.join(ROOT, ".bench_traces")
#: Prefix of the program's span annotations and operator scopes.
PREFIX = "flare:"

Trace = Dict[str, list]


# ---------------------------------------------------------------------------
# reading a trace
# ---------------------------------------------------------------------------


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: memoryview) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for anything length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not handled")
        yield field, value


def _text(value: Any) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_values(entries: Iterable[memoryview]) -> Iterator[memoryview]:
    """The values (field 2) of a protobuf map's entries."""
    for entry in entries:
        for f, v in _fields(entry):
            if f == 2:
                yield v


def _device_plane(name: str, plane: Dict[int, list]
                  ) -> Tuple[List[list], List[list]]:
    """(operations, programs) of one ``XPlane`` of a device.

    XPlane: 3 lines, 4 event_metadata (map id -> XEventMetadata: 1 id,
    2 name, 4 display_name, 5 stats), 5 stat_metadata (map id ->
    XStatMetadata: 1 id, 2 name).  XLine: 2 name, 3 timestamp_ns,
    4 events.  XEvent: 1 metadata_id, 2 offset_ps, 3 duration_ps.
    XStat: 1 metadata_id, 5 str_value, 7 ref_value (the id of a
    stat_metadata entry whose name is the value)."""
    stat_names: Dict[int, str] = {}
    for meta in _map_values(plane.get(5, ())):
        m = dict(_fields(meta))
        stat_names[m.get(1, 0)] = _text(m.get(2, b""))
    events: Dict[int, Tuple[str, Optional[str]]] = {}
    for meta in _map_values(plane.get(4, ())):
        ident, ev_name, tf_op = 0, "", None
        for f, v in _fields(meta):
            if f == 1:
                ident = v
            elif f == 2:
                ev_name = _text(v)
            elif f == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) == "tf_op":
                    tf_op = (_text(stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7)))
        events[ident] = (ev_name.split(" = ", 1)[0], scope(tf_op))
    ops: List[list] = []
    modules: List[list] = []
    for line in plane.get(3, ()):
        fields: Dict[int, list] = {}
        for f, v in _fields(line):
            fields.setdefault(f, []).append(v)
        line_name = _text(fields[2][0]) if 2 in fields else ""
        into = {"XLA Ops": ops, "XLA Modules": modules}.get(line_name)
        if into is None:
            continue
        base_ps = (fields[3][0] if 3 in fields else 0) * 1000
        for ev in fields.get(4, ()):
            e = dict(_fields(ev))
            start = (base_ps + e.get(2, 0)) * 1e-12
            end = start + e.get(3, 0) * 1e-12
            ev_name, ev_scope = events.get(e.get(1, 0), ("", None))
            if into is ops:
                ops.append([name, ev_name, start, end, ev_scope])
            else:
                modules.append([name, ev_name, start, end])
    return ops, modules


def load(path: str) -> Trace:
    """``{"spans": [[name, start, end, stats], ...], "host": [[name,
    start, end], ...], "device_ops": [[device, name, start, end,
    scope], ...], "modules": [[device, name, start, end], ...]}`` of one
    ``.xplane.pb``: the ``flare:`` host spans (name without the prefix,
    stats as a dict), the ``bench:`` annotations (as ``traces.load``
    gives them), and each device's operations with their ``flare:``
    scope (None where the operation has none) and programs."""
    from jax.profiler import ProfileData
    spans, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append([e.name[len(PREFIX):], e.start_ns * 1e-9,
                                  e.end_ns * 1e-9, dict(e.stats)])
                elif e.name.startswith(traces.PREFIX):
                    host.append([e.name, e.start_ns * 1e-9,
                                 e.end_ns * 1e-9])
    with open(path, "rb") as f:
        ops, modules = _device_planes(f.read())
    return {"spans": spans, "host": host, "device_ops": ops,
            "modules": modules}


def _device_planes(space: bytes) -> Tuple[List[list], List[list]]:
    """(operations, programs) of every device plane of a serialized
    ``XSpace`` (1 planes; XPlane: 2 name)."""
    ops: List[list] = []
    modules: List[list] = []
    for field, raw in _fields(memoryview(space)):
        if field != 1:
            continue
        plane: Dict[int, list] = {}
        for f, v in _fields(raw):
            plane.setdefault(f, []).append(v)
        name = _text(plane[2][0]) if 2 in plane else ""
        if name.startswith("/device:"):
            o, m = _device_plane(name, plane)
            ops += o
            modules += m
    return ops, modules


def newest() -> Optional[str]:
    paths = glob.glob(os.path.join(TRACES, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


@functools.lru_cache(maxsize=None)
def trace() -> Optional[Trace]:
    """The newest trace under ``.bench_traces/``, read once per process;
    None when there is none."""
    path = newest()
    return None if path is None else load(path)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def scope(tf_op: Optional[str]) -> Optional[str]:
    """The innermost ``flare:`` component of an operation's scope path
    (``jit(q3)/flare:join.probe/jit(searchsorted)/while:`` gives
    ``flare:join.probe``)."""
    if not tf_op:
        return None
    found = [part for part in tf_op.split("/") if part.startswith(PREFIX)]
    return found[-1].rstrip(":") if found else None


def window(tr: Trace) -> Optional[Tuple[float, float]]:
    """The traced window (the ``bench:traced`` annotation)."""
    for name, s, e in tr["host"]:
        if name == traces.WINDOW:
            return s, e
    return None


def durations(tr: Trace, name: str) -> List[float]:
    """Seconds of each ``flare:<name>`` span wholly inside the window."""
    win = window(tr)
    if win is None:
        return []
    lo, hi = win
    return [e - s for n, s, e, _ in tr["spans"]
            if n == name and s >= lo and e <= hi]


def percentile(values: List[float], q: float) -> Optional[float]:
    """The ``q``-th percentile, interpolated linearly between ranks (as
    numpy's default); None of no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def span_p95_ms(tr: Optional[Trace], name: str) -> Optional[float]:
    """95th percentile of span ``name`` in the window, in milliseconds;
    None when no such span was traced."""
    if tr is None:
        return None
    p95 = percentile(durations(tr, name), 95)
    return None if p95 is None else p95 * 1e3


def scope_seconds(tr: Trace, name: str, queries: Iterable[str]
                  ) -> Optional[float]:
    """Device seconds under scope ``name`` inside the calls of
    ``queries`` in the window: the union of the intervals of the
    operations that carry the scope and whose middle falls inside one
    of those calls' ``bench:<query>`` annotations, per device; None
    when no operation carries the scope there."""
    win = window(tr)
    if win is None or not tr["device_ops"]:
        return None
    lo, hi = win
    calls = [(n[len(traces.PREFIX):], s, e) for n, s, e in tr["host"]
             if n != traces.WINDOW and s >= lo and e <= hi]
    offset = traces.clock_offset(tr["modules"], calls)
    wanted = set(queries)
    inside = sorted((s, e) for q, s, e in calls if q in wanted)
    starts = [s for s, _ in inside]
    devices = sorted({d for d, *_ in tr["device_ops"]})
    per_dev: Dict[str, List[Tuple[float, float]]] = {d: [] for d in devices}
    for d, _, s, e, sc in tr["device_ops"]:
        if sc != name:
            continue
        s, e = s - offset, e - offset
        mid = 0.5 * (s + e)
        j = bisect.bisect_right(starts, mid) - 1
        if j >= 0 and mid <= inside[j][1]:
            per_dev[d].append((s, e))
    if not any(per_dev.values()):
        return None
    return sum(traces.total(traces.clip(traces.union(iv), lo, hi))
               for iv in per_dev.values()) / len(devices)


def stream_scope_s(run: Dict[str, Any], tr: Optional[Trace], name: str,
                   queries: Iterable[str], first: str) -> Optional[float]:
    """Device seconds under scope ``name`` in ``queries`` per traced
    stream: ``scope_seconds`` over the number of traced calls of the
    stream's ``first`` query."""
    streams = (run.get("trace") or {}).get("calls", {}).get(first)
    if tr is None or not streams:
        return None
    seconds = scope_seconds(tr, name, queries)
    return None if seconds is None else seconds / streams
