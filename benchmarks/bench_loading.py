"""Paper Table 1: loading times per TPC-H table, per reader.

Generic row-wise CSV (Spark-reader analogue) vs compiled schema-
specialized CSV (Flare CSV) vs flarecol binary columnar (Parquet
analogue), plus projected reads (Parquet's "load only required columns"
benefit, paper Fig. 10).
"""
from __future__ import annotations

import os
import tempfile

from benchmarks.common import emit, time_call
from repro.data import io as IO
from repro.relational.tpch import generate

SF = float(os.environ.get("BENCH_SF", "0.05"))


def run() -> None:
    tables = generate(SF)
    with tempfile.TemporaryDirectory() as d:
        for name in ("customer", "orders", "lineitem", "part",
                     "supplier", "nation"):
            tbl = tables[name]
            csvp = os.path.join(d, name + ".csv")
            fcp = os.path.join(d, name + ".fc")
            IO.to_csv(tbl, csvp)
            IO.write_flarecol(tbl, fcp)
            us_g = time_call(
                lambda: IO.read_csv_generic(csvp, tbl.schema),
                warmup=0, iters=3)
            us_c = time_call(
                lambda: IO.read_csv_compiled(csvp, tbl.schema),
                warmup=1, iters=3)
            us_f = time_call(lambda: IO.read_flarecol(fcp), iters=5)
            proj = tbl.schema.names[:2]
            us_fp = time_call(lambda: IO.read_flarecol(fcp, columns=proj),
                              iters=5)
            emit(f"load_{name}", us_c, rows=tbl.num_rows,
                 generic_csv_us=round(us_g, 1),
                 compiled_csv_us=round(us_c, 1),
                 flarecol_us=round(us_f, 1),
                 flarecol_proj_us=round(us_fp, 1),
                 compiled_speedup=round(us_g / us_c, 2),
                 flarecol_speedup=round(us_g / us_f, 2))


if __name__ == "__main__":
    from benchmarks.common import entry
    entry(run)
