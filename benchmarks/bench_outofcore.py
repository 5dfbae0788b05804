"""Out-of-core morsel execution: the memory-ceiling curve.

For each scale factor and each query (q1/q3/q6), sweeps a ladder of
declared memory budgets from "far below the monolithic working set" up
to "fits whole", and records what the morsel planner did at each rung:
the morsel size it chose, whether the monolithic program could have
satisfied the ceiling at all, runtime vs the unconstrained compiled
baseline, and the worst relative error against that baseline (the
correctness side of the curve).

The headline claim this validates: under a ceiling the monolithic
whole-table program CANNOT satisfy (``monolithic_fits: false`` rungs),
the morsel loop still answers, matches the baseline to float32
reassociation noise, and degrades smoothly -- runtime grows as the
budget (hence morsel size) shrinks, instead of falling off a cliff.

``$BENCH_OUTOFCORE_SFS`` (default ``0.01,0.05``) picks the scale
factors; ``$BENCH_OUTOFCORE_JSON`` (default ``bench_outofcore.json``)
lands the full morsel-size x SF curve as a CI artifact.
"""
from __future__ import annotations

import os

import numpy as np

from benchmarks.common import emit, time_call, write_report
from repro.core import FlareContext
from repro.core import lower as L
from repro.core import morsel as MO
from repro.relational import queries as Q

SFS = [float(s) for s in
       os.environ.get("BENCH_OUTOFCORE_SFS", "0.01,0.05").split(",")]
QUERIES = ("q1", "q3", "q6")
# budget ladder, bytes: 32 KiB .. 8 MiB (every SF's smallest table
# working set fits the top rung; the bottom rungs bind for all)
BUDGETS = [32 << 10, 128 << 10, 512 << 10, 2 << 20, 8 << 20]


def _worst_rel_err(base, got):
    worst = 0.0
    for k in base:
        x = np.atleast_1d(np.asarray(base[k]))
        y = np.atleast_1d(np.asarray(got[k]))
        if x.dtype.kind in "OSU":
            assert list(x) == list(y), k
            continue
        x, y = x.astype(np.float64), y.astype(np.float64)
        denom = np.maximum(np.abs(x), 1e-12)
        worst = max(worst, float(np.max(np.abs(x - y) / denom)))
    return worst


def run() -> None:
    report = {"budgets_bytes": BUDGETS, "sfs": SFS, "curve": []}
    for sf in SFS:
        ctx = FlareContext()
        Q.register_tpch(ctx, sf=sf)
        ctx.preload()
        rows = ctx.catalog.table("lineitem").num_rows
        for qname in QUERIES:
            df = Q.QUERIES[qname](ctx)
            mono_lowered = df.lower(engine="compiled")
            mono = mono_lowered.compile()
            base = mono.collect()
            t_mono = time_call(lambda: mono.collect(), warmup=1, iters=3)
            for budget in BUDGETS:
                try:
                    low = df.lower(engine="compiled",
                                   memory_budget=budget)
                except MO.MemoryBudgetError as ex:
                    report["curve"].append(
                        {"sf": sf, "query": qname, "budget": budget,
                         "infeasible": str(ex)})
                    continue
                node = MO.find_morsel_node(low.plan())
                morsel_rows = node.morsel_rows if node else None
                mono_fits = True
                if node is not None:
                    n_cols = len(L.required_scan_columns(
                        mono_lowered.plan(),
                        ctx.catalog)[id(node.spine)])
                    mono_fits = MO.working_set_bytes(
                        n_cols, rows) <= budget
                compiled = low.compile()
                got = compiled.collect()
                err = _worst_rel_err(base, got)
                # f32 accumulation-order noise grows with rows/morsel
                # count; 5e-3 is the suite-wide differential bar
                assert err < 5e-3, (qname, sf, budget, err)
                t = time_call(lambda: compiled.collect(), warmup=1,
                              iters=3)
                ratio = float(t / t_mono)
                emit(f"outofcore/{qname}/sf{sf}/budget{budget >> 10}K",
                     t, morsel_rows=morsel_rows or rows,
                     monolithic_fits=mono_fits, slowdown=round(ratio, 3))
                report["curve"].append(
                    {"sf": sf, "query": qname, "budget": budget,
                     "morsel_rows": morsel_rows,
                     "monolithic_fits": mono_fits,
                     "us_per_call": float(t),
                     "us_monolithic": float(t_mono),
                     "slowdown": ratio,
                     "worst_rel_err": err})
    ceilings = [r for r in report["curve"]
                if r.get("monolithic_fits") is False]
    assert ceilings, "no budget rung actually bound the monolithic path"
    report["bound_rungs"] = len(ceilings)
    write_report(report, "BENCH_OUTOFCORE_JSON",
                 default="bench_outofcore.json")


if __name__ == "__main__":
    from benchmarks.common import entry
    entry(run)
