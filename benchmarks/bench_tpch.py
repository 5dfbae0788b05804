"""Paper Fig. 9: the TPC-H suite across engines, plus compile times.

Each reproduced query runs on the volcano (interpreted / Postgres
analogue), stage (Spark analogue) and whole-query compiled (Flare L2)
engines, driven through the explicit stages API so compile time and run
time are reported separately (paper section 6.1: "less than 1.5s for all
queries", Flare ~20% above Spark).  The prepared-query templates
(q6/q14/q19 selectivity variants) additionally report the compile-cache
hit rate across bindings: one compile, N executions.

``--native`` adds a native-kernel-dispatch row per query
(``df.lower(engine="compiled", native=True)``, repro.native) and writes
compiled-vs-native times plus the per-query dispatch reports to
``$BENCH_TPCH_JSON`` (default ``bench_tpch.json``).

``--parallel`` adds a sharded-engine row per query
(``df.lower(engine="parallel")``, repro.core.parallel) over a data mesh
of every host device -- set ``XLA_FLAGS=--xla_force_host_platform_
device_count=N`` for a simulated N-shard run.
"""
from __future__ import annotations

import argparse
import os

from benchmarks.common import emit, time_call, write_report
from repro.core import CompileCache, FlareContext
from repro.relational import queries as Q

SF = float(os.environ.get("BENCH_SF", "0.05"))


def run(native: bool = False, parallel: bool = False) -> None:
    ctx = FlareContext()
    Q.register_tpch(ctx, sf=SF)
    ctx.preload()

    report = {"sf": SF, "queries": {}}
    with_tuple = os.environ.get("BENCH_TUPLE", "1") == "1"
    for name, qf in Q.QUERIES.items():
        q = qf(ctx)
        derived = {}
        if with_tuple:  # the truly-interpreted Postgres row (one pass)
            us_t = time_call(lambda: q.collect(engine="tuple"),
                             warmup=0, iters=1)
            derived["tuple_us"] = round(us_t, 1)
        us_v = time_call(lambda: q.collect(engine="volcano"), iters=3)
        us_s = time_call(lambda: q.collect(engine="stage"), iters=5)
        # compile time measured cache-cold through the stages split
        compiled = q.lower(engine="compiled").compile(cache=CompileCache())
        us_c = time_call(compiled.collect, iters=7)
        if with_tuple:
            derived["speedup_vs_tuple"] = round(
                derived["tuple_us"] / us_c, 1)
        qrep = {"volcano_us": round(us_v, 1), "stage_us": round(us_s, 1),
                "compiled_us": round(us_c, 1)}
        if native:
            nlowered = q.lower(engine="compiled", native=True)
            ncompiled = nlowered.compile(cache=CompileCache())
            us_n = time_call(ncompiled.collect, iters=7)
            drep = nlowered.dispatch_report()
            derived["native_us"] = round(us_n, 1)
            derived["native_fired"] = \
                ";".join(drep.fired_patterns()) or "none"
            derived["native_vs_compiled"] = round(us_c / us_n, 2)
            qrep.update({"native_us": round(us_n, 1),
                         "native_vs_compiled": round(us_c / us_n, 2),
                         "dispatch": drep.to_dict()})
        if parallel:
            pcompiled = q.lower(engine="parallel").compile(
                cache=CompileCache())
            us_p = time_call(pcompiled.collect, iters=7)
            derived["parallel_us"] = round(us_p, 1)
            derived["parallel_vs_compiled"] = round(us_c / us_p, 2)
            qrep.update({"parallel_us": round(us_p, 1),
                         "parallel_vs_compiled": round(us_c / us_p, 2)})
        report["queries"][name] = qrep
        emit(f"tpch_{name}", us_c, volcano_us=round(us_v, 1),
             stage_us=round(us_s, 1),
             speedup_vs_volcano=round(us_v / us_c, 2),
             speedup_vs_stage=round(us_s / us_c, 2),
             lower_s=round(compiled.stats.lower_s, 3),
             compile_s=round(compiled.stats.compile_s, 3),
             compile_total_s=round(compiled.stats.trace_compile_s, 3),
             **derived)

    # q22 (scalar subquery, two-phase prepared template)
    binding = Q.q22_params(ctx, "volcano")
    q22c = Q.q22(ctx).lower(engine="compiled").compile()
    us_v = time_call(lambda: Q.q22(ctx).collect(
        engine="volcano", params=binding), iters=3)
    us_c = time_call(lambda: q22c.collect(**binding), iters=5)
    emit("tpch_q22", us_c, volcano_us=round(us_v, 1),
         speedup_vs_volcano=round(us_v / us_c, 2))

    # prepared templates: one compile serves every selectivity variant
    for name, tf in Q.TEMPLATES.items():
        cache = CompileCache()
        tmpl = tf(ctx)
        bindings = Q.TEMPLATE_BINDINGS[name]
        run_us = []
        for b in bindings:
            compiled = tmpl.lower(engine="compiled",
                                  native=native).compile(cache=cache)
            run_us.append(time_call(lambda: compiled.collect(**b),
                                    iters=5))
        emit(f"tpch_{name}_prepared", sum(run_us) / len(run_us),
             bindings=len(bindings),
             compiles=cache.misses,
             cache_hit_rate=round(cache.hit_rate, 3),
             native=int(native))

    if native or parallel:
        from repro.persist import store as PS
        report["store"] = PS.live_store_stats()
        write_report(report, "BENCH_TPCH_JSON", default="bench_tpch.json")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--native", action="store_true",
                    help="add native-kernel-dispatch rows per query and "
                         "write the JSON report with dispatch details")
    ap.add_argument("--parallel", action="store_true",
                    help="add sharded parallel-engine rows per query "
                         "(data mesh over every host device)")
    args = ap.parse_args(argv)
    run(native=args.native, parallel=args.parallel)


if __name__ == "__main__":
    from benchmarks.common import entry
    entry(main)
