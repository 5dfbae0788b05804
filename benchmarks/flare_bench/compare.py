"""The comparison that decides ``correct``: an answer against the
reference's whole answer.

Two numbers come out of it, each held to its own limit:

* ``rel_gap``: the widest relative gap, over every float value of
  every answer compared, between the program's value and the
  reference's, ``|got - want| / |want|``;
* ``mismatched``: differences in shape, keys, strings, integers (counts
  among them) or row order (``reference.ORDER``), each column counted
  once (limit 0).

Rows are matched by the query's key columns (``reference.KEYS``).  A
top-k answer (``reference.TOPK``) must hold the reference's first k
ranking values rank by rank, and each row must be the reference's row
for its key, so a tie broken the other way by rounding is no mismatch.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmarks.flare_bench.reference import KEYS, ORDER, TOPK, Answer


def _gap(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    diff = np.abs(got - want)
    scale = np.abs(want)
    with np.errstate(divide="ignore", invalid="ignore"):
        g = np.where(diff == 0.0, 0.0, diff / scale)
    if not np.all(np.isfinite(g)):
        return float("inf")
    return float(g.max(initial=0.0))


def _is_exact(v: np.ndarray) -> bool:
    """Strings, integers (keys, dates, counts) and booleans compare
    exactly; floats by their relative gap."""
    return v.dtype == object or v.dtype.kind in "Uiub"


def compare(query: str, got: Answer, want: Answer) -> Tuple[float, List[str]]:
    """(widest relative gap, what differs exactly) of one answer."""
    got = {k: np.atleast_1d(np.asarray(v)) for k, v in got.items()}
    want = {k: np.atleast_1d(np.asarray(v)) for k, v in want.items()}
    if set(got) != set(want):
        return 0.0, [f"{query}: columns {sorted(got)} != {sorted(want)}"]
    keys = KEYS[query]
    n_got = len(next(iter(got.values())))
    n_want = len(next(iter(want.values())))
    limit, rank_col = TOPK.get(query, (None, None))
    want_rows = n_want if limit is None else min(limit, n_want)
    bad: List[str] = []
    if n_got != want_rows:
        return 0.0, [f"{query}: {n_got} rows, reference {want_rows}"]
    if not keys:
        order = np.arange(n_got)
    else:
        index = {tuple(want[k][j] for k in keys): j for j in range(n_want)}
        order = []
        for i in range(n_got):
            j = index.get(tuple(got[k][i] for k in keys))
            if j is None:
                bad.append(f"{query}: row {i} key "
                           f"{tuple(got[k][i] for k in keys)} not in "
                           "the reference")
                return 0.0, bad
            order.append(j)
        order = np.asarray(order)
        if len(set(order.tolist())) != len(order):
            return 0.0, [f"{query}: a key repeats"]
    gap = 0.0
    for name in got:
        g, w = got[name], want[name][order]
        if name in keys or _is_exact(g) or _is_exact(w):
            if list(g) != list(w):
                bad.append(f"{query}.{name}: differs")
        else:
            gap = max(gap, _gap(g, w))
    if rank_col is not None:
        ranked = np.sort(np.asarray(want[rank_col], np.float64))[::-1]
        gap = max(gap, _gap(got[rank_col], ranked[:want_rows]))
    if not _ordered(got, ORDER.get(query, ())):
        bad.append(f"{query}: rows out of order")
    return gap, bad


def _ordered(got: Answer, spec) -> bool:
    """Rows sorted by ``spec``, a list of (column, +1 ascending or -1
    descending)."""
    n = len(next(iter(got.values())))
    for i in range(n - 1):
        for name, sign in spec:
            a, b = got[name][i], got[name][i + 1]
            if a != b:
                if (a < b) != (sign > 0):
                    return False
                break
    return True


def merge(results: List[Tuple[float, List[str]]]) -> Dict[str, float]:
    """The cell's compared numbers over every answer compared."""
    gap = max((g for g, _ in results), default=0.0)
    bad = sum(len(b) for _, b in results)
    return {"rel_gap": gap, "mismatched": float(bad)}
