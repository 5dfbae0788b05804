"""Paper Figs. 8/13/14: heterogeneous workloads (relational ETL + ML).

The paper's Level 3 claim: compiling relational ETL *together with* the
iterative ML kernel (k-means, LogReg, GDA) is order-of-magnitude faster
than Spark's treat-UDFs-as-black-boxes execution.  Both configurations
now run through the stages API on the SAME ``df.train(...)`` plan:

* ``staged`` (``engine="stage"``): the relational half materialises
  through the host, then the kernel runs as its own jitted stage --
  Spark's per-stage execution of ML pipelines,
* ``fused`` (``engine="compiled"``, Flare L3): ONE XLA program holding
  ETL + the full ``until_converged`` training loop (lax.while_loop).

Emits the usual CSV rows and (for CI artifacts) a JSON report at
``$BENCH_ML_JSON`` (default ``bench_ml.json``).
"""
from __future__ import annotations

import os

import jax
import numpy as np

from benchmarks.common import emit, time_call, write_report
from repro.core import FlareContext, col
from repro.relational.table import Table

N_DOCS = int(os.environ.get("BENCH_ML_ROWS", "20000"))


def _features_table(n: int, d: int = 8, seed: int = 0) -> Table:
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 5, (4, d))
    assign = rng.integers(0, 4, n)
    x = centers[assign] + rng.normal(0, 1, (n, d))
    data = {f"f{i}": x[:, i] for i in range(d)}
    data["label"] = (assign % 2).astype(np.int32)
    data["quality"] = rng.uniform(0, 1, n)
    return Table.from_arrays(data)


def _bench_pipeline(name: str, train_df, leaf) -> dict:
    """Time the same plan fused (compiled) vs staged (stage engine)."""
    rows = {}
    for engine in ("compiled", "stage"):
        compiled = train_df.lower(engine=engine).compile()
        us = time_call(
            lambda: jax.block_until_ready(leaf(compiled())), iters=5)
        rows[engine] = {
            "us_per_call": round(us, 1),
            "lower_s": round(compiled.stats.lower_s, 4),
            "compile_s": round(compiled.stats.compile_s, 4),
            "cache_hit": compiled.stats.cache_hit,
        }
    speedup = rows["stage"]["us_per_call"] / rows["compiled"]["us_per_call"]
    emit(f"ml_{name}_fused", rows["compiled"]["us_per_call"],
         staged_us=rows["stage"]["us_per_call"],
         speedup=round(speedup, 2))
    rows["speedup"] = round(speedup, 2)
    return rows


def run() -> None:
    ctx = FlareContext()
    ctx.register("points", _features_table(N_DOCS))
    ctx.preload("points")
    feat_cols = [f"f{i}" for i in range(8)]
    etl = ctx.table("points").filter(col("quality") > 0.1)

    report = {"rows": N_DOCS, "pipelines": {}}

    # ---- k-means (Fig 8) ----------------------------------------------------
    km = etl.to_matrix(*feat_cols).train("kmeans", k=4, max_iter=50)
    report["pipelines"]["kmeans"] = _bench_pipeline(
        "kmeans", km, lambda r: r.centroids)

    # ---- LogReg (Fig 13/14) -------------------------------------------------
    lr = etl.train("logreg", columns=feat_cols, label="label",
                   max_iter=100)
    report["pipelines"]["logreg"] = _bench_pipeline(
        "logreg", lr, lambda r: r.weights)

    # ---- GDA (Fig 13) -------------------------------------------------------
    gda = etl.train("gda", columns=feat_cols, label="label")
    report["pipelines"]["gda"] = _bench_pipeline(
        "gda", gda, lambda r: r.sigma)

    write_report(report, "BENCH_ML_JSON", default="bench_ml.json")


if __name__ == "__main__":
    from benchmarks.common import entry
    entry(run)
