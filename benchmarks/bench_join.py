"""Paper Fig. 6: lineitem |><| orders under three join strategies,
plus the build-side index cache split (DESIGN.md section 10).

Paper numbers: Spark sort-merge 14,937 ms; Spark broadcast-hash 4,775 ms
(2,232 ms of it in the exchange operator); Flare in-memory hash join
136 ms.  Mapping here:

  * ``stage`` engine + ``sortmerge``   -> Spark sort-merge join,
  * ``stage`` engine + ``sorted``      -> Spark broadcast-hash join (the
    host round-trips between stages play the exchange),
  * ``compiled``, ``join_index=False`` -> Flare whole-query join with the
    build-side argsort INSIDE the program (rebuilt per execution -- the
    cold baseline),
  * ``compiled``, warm index           -> the same program probing the
    preloaded IndexCache entry: steady-state executions never re-sort
    the build side (the paper's load-time/execution-time split).

Emits the usual ``name,us,derived`` rows and, when ``$BENCH_JOIN_JSON``
is set, a JSON artifact with the cold/warm split, the one-off index
build time, and the per-join index decisions -- uploaded by CI next to
bench_tpch.json / bench_ml.json.
"""
from __future__ import annotations

import os
import time

from benchmarks.common import emit, time_call, write_report
from repro.core import FlareContext
from repro.relational import queries as Q

SF = float(os.environ.get("BENCH_SF", "0.05"))
ITERS = int(os.environ.get("BENCH_JOIN_ITERS", "9"))


def run() -> None:
    ctx = FlareContext()
    Q.register_tpch(ctx, sf=SF)
    t0 = time.perf_counter()
    ctx.preload("lineitem", "orders", indexes=False)
    load_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ctx.preload("orders")  # index build on the declared-unique PK
    index_build_s = time.perf_counter() - t0

    report = {
        "sf": SF,
        "lineitem_rows": ctx.catalog.table("lineitem").num_rows,
        "orders_rows": ctx.catalog.table("orders").num_rows,
        "column_load_s": round(load_s, 4),
        "index_build_s": round(index_build_s, 4),
    }

    # -- Spark-analogue stage engine rows (Fig. 6) ---------------------------
    q_sm = Q.join_micro(ctx, strategy="sortmerge")
    sm = q_sm.lower(engine="stage").compile()
    us_sm = time_call(sm, iters=5)
    emit("join_sortmerge_stage", us_sm, paper_row="spark_sort_merge")

    q_h = Q.join_micro(ctx, strategy="sorted")
    st = q_h.lower(engine="stage").compile()
    us_h = time_call(st, iters=5)
    emit("join_hash_stage", us_h, paper_row="spark_broadcast_hash")

    # -- compiled, cold: build-side argsort re-runs inside the program -------
    cold = q_h.lower(engine="compiled", join_index=False).compile()
    us_cold = time_call(cold, iters=ITERS)
    emit("join_compiled_argsort", us_cold, paper_row="flare_inmem_join",
         speedup_vs_sortmerge=round(us_sm / us_cold, 2),
         speedup_vs_hash_stage=round(us_h / us_cold, 2))

    # -- compiled, warm: probe the cached index ------------------------------
    lowered = q_h.lower(engine="compiled")
    rep = lowered.dispatch_report()
    warm = lowered.compile()
    warm()  # first call: index fetch (already preloaded) + device warmup
    us_warm = time_call(warm, iters=ITERS)
    warm_speedup = round(us_cold / us_warm, 2)
    emit("join_compiled_indexed", us_warm, paper_row="flare_inmem_join",
         speedup_vs_argsort=warm_speedup,
         speedup_vs_hash_stage=round(us_h / us_warm, 2))

    report.update({
        "stage_sortmerge_us": round(us_sm, 1),
        "stage_hash_us": round(us_h, 1),
        "compiled_cold_argsort_us": round(us_cold, 1),
        "compiled_warm_indexed_us": round(us_warm, 1),
        "warm_vs_cold_speedup": warm_speedup,
        "index_cache": {
            "hits": ctx.cache.indexes.hits,
            "misses": ctx.cache.indexes.misses,
            "hit_rate": round(ctx.cache.indexes.hit_rate, 3),
        },
        "join_index_decisions": (rep.to_dict()["joins_cached"]
                                 + rep.to_dict()["joins_rebuilt"])
        if rep else [],
    })

    write_report(report, "BENCH_JOIN_JSON")  # opt-in artifact


if __name__ == "__main__":
    from benchmarks.common import entry
    entry(run)
