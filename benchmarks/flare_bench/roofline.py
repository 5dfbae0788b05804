"""Bytes a query must read, and its share of the chip's HBM roofline.

The bytes come from the query's columns, as the TPC-H text names them,
and each column's itemsize on the device, not from how the program
implements the query: whatever runs, it has to read these columns once.
The peaks are those of ``peaks.json``, keyed by JAX's ``device_kind``.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional


HERE = os.path.dirname(os.path.abspath(__file__))

#: Columns each measured query reads.
COLUMNS = {
    "q1": ("lineitem", ("l_shipdate", "l_returnflag", "l_linestatus",
                        "l_quantity", "l_extendedprice", "l_discount",
                        "l_tax")),
    "q6": ("lineitem", ("l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice")),
}

#: On the device, float64 host columns are float32 and string columns
#: int32 codes: JAX runs without 64-bit types.
DEVICE_ITEMSIZE = {"int32": 4, "date": 4, "string": 4, "float64": 4,
                   "float32": 4}


def peaks(kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def query_bytes(query: str, rows: int, dtypes: Dict[str, str]) -> int:
    """Bytes of the columns ``query`` reads, at ``rows`` rows, given the
    logical type of each column."""
    _, cols = COLUMNS[query]
    return int(rows) * sum(DEVICE_ITEMSIZE[dtypes[c]] for c in cols)


def share(nbytes: float, seconds: Optional[float],
          kind: str) -> Optional[float]:
    """Percent of the HBM roofline: the least time the bytes need at
    peak bandwidth over the time measured.  None without a time."""
    if not seconds or seconds <= 0.0:
        return None
    return float(100.0 * nbytes / peaks(kind)["hbm_bytes_per_s"] / seconds)
