"""Arithmetic shared by the per-layer metric readers in
``layer_metrics/``: each reader is ``read(run) -> float | None`` over
the run record ``harness.run_cell`` builds, and returns None where its
run has nothing for it to read."""
from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.flare_bench import roofline

Run = Dict[str, Any]


def idle_share(run: Run) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device."""
    tr = run["trace"]
    if tr is None or tr["window_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def _bytes(run: Run, query: str) -> int:
    table, _ = roofline.COLUMNS[query]
    cols = run["tables"][table]
    rows = len(next(iter(cols.values())).data)
    return roofline.query_bytes(query, rows,
                                {n: c.dtype for n, c in cols.items()})


def stream_roofline(run: Run, query: str) -> Optional[float]:
    """Share of the HBM roofline of one call of ``query`` in a stream:
    the device time of the operations that ran while the call was open
    on the host, over the calls traced."""
    tr = run["trace"]
    if tr is None or not tr["calls"].get(query):
        return None
    per_call = tr["device_s"][query] / tr["calls"][query]
    return roofline.share(_bytes(run, query), per_call,
                          run["device"]["kind"])


def dispatch_roofline(run: Run, query: str, program: str
                      ) -> Optional[float]:
    """Share of the HBM roofline of one batched dispatch of a served
    template: the columns are read at least once per dispatch, and the
    dispatch's time is that of its program (``program``, the XLA module
    name) on the device."""
    tr = run["trace"]
    if tr is None or not tr["programs"].get(program):
        return None
    count, seconds = tr["programs"][program]
    return roofline.share(_bytes(run, query), seconds / count,
                          run["device"]["kind"])


def measured(run: Run, name: str) -> Optional[float]:
    value = run["measured"].get(name)
    return None if value is None else float(value)
