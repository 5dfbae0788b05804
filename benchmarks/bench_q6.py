"""Paper Fig. 4 + Fig. 5: TPC-H Q6 across execution strategies.

Reproduces the paper's running experiment: Q6 'direct from CSV' vs
preloaded, interpreted (volcano = the Postgres row, and the paper's
Spark-without-codegen story) vs stage-granular (Spark/Tungsten analogue:
pipelines jit'ed per stage, host round-trips between stages) vs
whole-query compiled (Flare L2) vs the hand-scheduled Pallas kernel (the
paper's hand-written C row).

``--native`` additionally runs Q6 through the kernel-dispatch subsystem
(``df.lower(engine="compiled", native=True)``, repro.native): the
filter+aggregate fragment lowers onto the generalized Pallas kernel
inside the whole-query program.  Compiled-vs-native times plus the
dispatch report land in a JSON report at ``$BENCH_Q6_JSON`` (default
``bench_q6.json``), consistent with bench_ml.py's CI artifact.

Claims validated (EXPERIMENTS.md section Paper-validation):
  * preload >> direct CSV,
  * whole-query compiled is order(s)-of-magnitude over interpreted,
  * whole-query compiled ~= hand-written kernel (paper: "exactly the
    same performance as the hand-written C code").
"""
from __future__ import annotations

import argparse
import os
import tempfile

import jax
import numpy as np

from benchmarks.common import emit, time_call, write_report
from repro.core import CompileCache, FlareContext
from repro.data import io as IO
from repro.kernels.filter_agg import ops as FA
from repro.relational import queries as Q
from repro.relational.tpch import date

SF = float(os.environ.get("BENCH_SF", "0.05"))


def run(native: bool = False) -> None:
    ctx = FlareContext()
    Q.register_tpch(ctx, sf=SF)
    li = ctx.catalog.table("lineitem")
    n = li.num_rows

    # --- direct CSV: load + execute (the paper's 24.4s row) -----------------
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "lineitem.csv")
        IO.to_csv(li, path)

        # shared across iterations: the template key matches across CSV
        # re-reads (same metadata), so only the first iteration compiles --
        # the measurement stays load + execute, as before
        csv_cache = CompileCache()

        def direct():
            tbl = IO.read_csv_compiled(path, li.schema)
            c2 = FlareContext()
            for name in ctx.catalog.names():
                c2.register(name, ctx.catalog.table(name))
            c2.register("lineitem", tbl)
            Q.q6(c2).lower(engine="compiled").compile(
                cache=csv_cache).collect()

        us_direct = time_call(direct, warmup=0, iters=3)
    emit("q6_direct_csv", us_direct, rows=n, sf=SF)

    # --- preloaded engines ---------------------------------------------------
    ctx.preload("lineitem")
    q6 = Q.q6(ctx)
    # tuple-at-a-time Volcano: the paper's truly-interpreted row (Postgres
    # / per-tuple iterator glue).  One warm run, few iters -- it is slow,
    # that is the measurement.
    us_tuple = time_call(lambda: q6.collect(engine="tuple"), warmup=0,
                         iters=1)
    emit("q6_tuple_volcano", us_tuple, engine="row_interpreted")
    us_volcano = time_call(lambda: q6.collect(engine="volcano"), iters=5)
    emit("q6_volcano", us_volcano, engine="vectorized_interpreted")
    us_stage = time_call(lambda: q6.collect(engine="stage"), iters=9)
    emit("q6_stage", us_stage, engine="spark_analogue")
    # whole-query compiled, through the explicit stages split: compile
    # once (AOT, measured), then time pure execution
    cq6 = q6.lower(engine="compiled").compile(cache=CompileCache())
    us_comp = time_call(cq6.collect, iters=9)
    emit("q6_compiled", us_comp, engine="flare_L2",
         lower_s=round(cq6.stats.lower_s, 3),
         compile_s=round(cq6.stats.compile_s, 3),
         speedup_vs_tuple=round(us_tuple / us_comp, 1),
         speedup_vs_volcano=round(us_volcano / us_comp, 2),
         speedup_vs_stage=round(us_stage / us_comp, 2))

    # prepared-query reuse: ONE compiled Q6 template across selectivity
    # bindings (the TPC-H substitution parameters as runtime arguments)
    cache = CompileCache()
    tmpl = Q.q6_template(ctx)
    per_binding = []
    for b in Q.TEMPLATE_BINDINGS["q6"]:
        prepared = tmpl.lower(engine="compiled").compile(cache=cache)
        per_binding.append(time_call(lambda: prepared.collect(**b),
                                     iters=9))
    emit("q6_prepared_template", sum(per_binding) / len(per_binding),
         bindings=len(per_binding), compiles=cache.misses,
         cache_hit_rate=round(cache.hit_rate, 3),
         vs_unparameterized=round(
             (sum(per_binding) / len(per_binding)) / us_comp, 2))

    # --- native kernel dispatch (repro.native, --native) ---------------------
    report = {"sf": SF, "rows": n, "compiled_us": round(us_comp, 1)}
    if native:
        nlowered = q6.lower(engine="compiled", native=True)
        ncompiled = nlowered.compile(cache=CompileCache())
        us_native = time_call(ncompiled.collect, iters=9)
        drep = nlowered.dispatch_report()
        emit("q6_native", us_native,
             fired=";".join(drep.fired_patterns()) or "none",
             native_vs_compiled=round(us_comp / us_native, 2),
             lower_s=round(ncompiled.stats.lower_s, 3),
             compile_s=round(ncompiled.stats.compile_s, 3))
        # prepared NATIVE template: param() bindings ride as
        # scalar-prefetch arguments -> still one compilation
        ncache = CompileCache()
        native_binding_us = []
        for b in Q.TEMPLATE_BINDINGS["q6"]:
            prep = tmpl.lower(engine="compiled",
                              native=True).compile(cache=ncache)
            native_binding_us.append(
                time_call(lambda: prep.collect(**b), iters=9))
        emit("q6_native_prepared",
             sum(native_binding_us) / len(native_binding_us),
             bindings=len(native_binding_us), compiles=ncache.misses,
             cache_hit_rate=round(ncache.hit_rate, 3))
        report.update({
            "native_us": round(us_native, 1),
            "native_vs_compiled": round(us_comp / us_native, 2),
            "native_prepared_us": round(
                sum(native_binding_us) / len(native_binding_us), 1),
            "native_prepared_compiles": ncache.misses,
            "dispatch": drep.to_dict(),
        })

    # --- hand-scheduled kernel (the hand-written C row) ----------------------
    import jax.numpy as jnp
    qty = jnp.asarray(li["l_quantity"], jnp.float32)
    price = jnp.asarray(li["l_extendedprice"], jnp.float32)
    disc = jnp.asarray(li["l_discount"], jnp.float32)
    ship = jnp.asarray(li["l_shipdate"], jnp.int32)
    kw = dict(date_lo=date("1994-01-01"), date_hi=date("1995-01-01"),
              disc_lo=0.05, disc_hi=0.07, qty_hi=24.0)

    def kernel():
        return jax.block_until_ready(
            FA.filter_agg_q6(qty, price, disc, ship, **kw))

    us_kernel = time_call(kernel, iters=9)
    # NOTE: on this CPU container the kernel runs in interpret mode --
    # the timing is a correctness artifact, not a TPU speed claim.
    emit("q6_pallas_kernel", us_kernel, mode="interpret",
         compiled_vs_kernel=round(us_comp / us_kernel, 2))

    # --- Fig. 5 analogue: where does stage time go? ---------------------------
    emit("q6_stage_overhead", us_stage - us_comp,
         overhead_frac=round((us_stage - us_comp) / us_stage, 3))

    if native:  # JSON report only with --native (mirrors bench_tpch)
        write_report(report, "BENCH_Q6_JSON", default="bench_q6.json")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--native", action="store_true",
                    help="also run Q6 via native kernel dispatch "
                         "(df.lower(native=True)) and report the "
                         "dispatch report in the JSON output")
    args = ap.parse_args(argv)
    run(native=args.native)


if __name__ == "__main__":
    from benchmarks.common import entry
    entry(main)
