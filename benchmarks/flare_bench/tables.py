"""The benchmark's generated arrays as the program's tables."""
from __future__ import annotations

from typing import Any, Dict

from benchmarks.flare_bench.data import tpch as G


def generate(config: Dict[str, Any], seed: int) -> G.Tables:
    """The configuration's tables at ``seed``: its scale factor, and one
    distinct string per row in the text columns its ``full_text``
    names."""
    return G.generate(float(config["scale_factor"]), seed,
                      full_text=config.get("full_text", ()))


def to_tables(raw: G.Tables) -> Dict[str, "object"]:
    """Wrap each generated table as a ``repro.relational.table.Table``
    (no copy of a column that already has its device-side numpy type)."""
    from repro.relational import table as T
    out = {}
    for name, cols in raw.items():
        columns, fields = {}, []
        for cname, c in cols.items():
            columns[cname] = T.Column(c.data, c.dtype, c.dictionary)
            fields.append(T.Field(cname, c.dtype, c.domain, c.unique))
        out[name] = T.Table(columns, T.Schema(fields))
    return out
