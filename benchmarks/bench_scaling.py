"""Paper Figs. 11/12: parallel scaling + data-partitioning placement.

Runs Q6 and Q1 through the first-class ``parallel`` engine
(``df.lower(engine="parallel", mesh=...)``: row-partitioned spine scans,
psum/pmin/pmax-merged partial aggregates -- the paper's OpenMP/NUMA
scheme on a device mesh) at 1/2/4/8 shards, as far as the process has
devices.  Everything runs in ONE process over ``make_data_mesh(n)``
subsets of the visible devices: an accelerator belongs to one process
at a time, so per-shard-count child processes could not share a chip
host.  On the CPU backend, simulate devices from the command line:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src:. python benchmarks/bench_scaling.py

Reports absolute time AND the paper's COST lens: speedup vs the
1-shard program.  ``$BENCH_SCALING_JSON`` (default
``bench_scaling.json``) gets the full per-shard-count table -- compile
split included -- as a CI artifact next to bench_ml/bench_q6.

Simulated CPU devices share the same physical cores, so there a >1x
speedup is impossible: what such a run validates is that the
mesh-partitioned program adds little overhead over the 1-shard one.
"""
from __future__ import annotations

import os

from benchmarks.common import emit, entry, time_call, write_report

SF = float(os.environ.get("BENCH_SF", "0.05"))
SHARDS = (1, 2, 4, 8)


def run() -> None:
    import jax

    from repro.core import FlareContext
    from repro.launch.mesh import make_data_mesh
    from repro.relational import queries as Q

    ctx = FlareContext()
    Q.register_tpch(ctx, sf=SF)
    ctx.preload()
    n_avail = len(jax.devices())
    report = {"sf": SF, "engine": "parallel", "devices": n_avail,
              "shards": {}}
    base = {}
    for ndev in (n for n in SHARDS if n <= n_avail):
        mesh = make_data_mesh(ndev)
        for q in ("q6", "q1"):
            compiled = Q.QUERIES[q](ctx).lower(engine="parallel",
                                               mesh=mesh).compile()
            us = time_call(compiled, warmup=1, iters=5)
            base.setdefault(q, us)
            speedup = round(base[q] / us, 2)
            emit(f"scaling_{q}_{ndev}dev", us, speedup=speedup,
                 compile_s=round(compiled.stats.compile_s, 3))
            report["shards"].setdefault(str(ndev), {})[q] = {
                "run_us": float(us),
                "lower_s": round(compiled.stats.lower_s, 3),
                "compile_s": round(compiled.stats.compile_s, 3),
                "speedup_vs_1dev": speedup}
    write_report(report, "BENCH_SCALING_JSON",
                 default="bench_scaling.json")


if __name__ == "__main__":
    entry(run)
