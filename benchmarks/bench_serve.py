"""Serving throughput: vmap-coalesced batches vs per-request dispatch.

Flare's deployment mode (paper section 5) serves compiled templates to
many tenants; the repo's claim (DESIGN.md section 11) is that coalescing
same-template requests into ONE vmapped program beats dispatching each
binding on its own once batches are a few requests deep -- per-request
dispatch overhead, not compute, dominates Spark-class servers under
concurrency.

For each template and each batch size B this benchmark serves the same B
random bindings (a) sequentially, one ``Compiled.result`` per request,
and (b) through :class:`repro.serve.QueryServer` -- admit, coalesce,
one dispatch, deferred per-request sync -- and reports requests/sec plus
p50/p99 request latency for both.  When ``$BENCH_SERVE_JSON`` is set the
JSON artifact also records batch occupancy and the compile-cache proof
that the whole run compiled exactly one batched executable per
(template, bucket).
"""
from __future__ import annotations

import os
import time

from benchmarks.common import emit, write_report
from repro.core import FlareContext
from repro.core import engines as ENG
from repro.relational import queries as Q
from repro.serve import QueryServer, ServeStats
from repro.serve.stats import percentile

SF = float(os.environ.get("BENCH_SF", "0.02"))
ITERS = int(os.environ.get("BENCH_SERVE_ITERS", "7"))
BATCHES = [1, 4, 8, 16]
TEMPLATES = [t for t in os.environ.get("BENCH_SERVE_TEMPLATES",
                                       "q6,q14,q19").split(",") if t]


def _percentiles_ms(lat_s):
    return (round(percentile(lat_s, 50) * 1e3, 3),
            round(percentile(lat_s, 99) * 1e3, 3))


def serve_sequential(compiled, bindings, iters):
    """One device dispatch per request (the pre-serving posture)."""
    lat, total = [], 0.0
    for _ in range(iters):
        t_iter = time.perf_counter()
        for b in bindings:
            t0 = time.perf_counter()
            compiled.result(**b).compact()
            lat.append(time.perf_counter() - t0)
        total += time.perf_counter() - t_iter
    return len(bindings) * iters / total, lat


def serve_batched(server, name, bindings, iters):
    """Admit all requests, coalesce into one vmapped dispatch, sync per
    request (the server's steady state)."""
    total = 0.0
    server.stats = ServeStats()  # measure steady state only
    for _ in range(iters):
        t_iter = time.perf_counter()
        futs = [server.submit(name, **b) for b in bindings]
        server.flush()
        for f in futs:
            f.result().compact()
        total += time.perf_counter() - t_iter
    return len(bindings) * iters / total, server.stats


def run() -> None:
    ctx = FlareContext()
    Q.register_tpch(ctx, sf=SF)
    ctx.preload()
    server = QueryServer(ctx, templates={n: Q.TEMPLATES[n]
                                         for n in TEMPLATES})

    report = {"sf": SF, "iters": ITERS, "templates": {}}
    wins_at_4plus = 0
    for name in TEMPLATES:
        compiled = server.compiled_for(name)
        rows = []
        for B in BATCHES:
            bindings = Q.random_bindings(name, B, seed=len(rows))
            # warm both paths: base + batched executables compile here,
            # so the timed loops measure serving, not compilation
            compiled.result(**bindings[0])
            server.serve([(name, b) for b in bindings])
            seq_rps, seq_lat = serve_sequential(compiled, bindings, ITERS)
            bat_rps, stats = serve_batched(server, name, bindings, ITERS)
            seq_p50, seq_p99 = _percentiles_ms(seq_lat)
            speedup = round(bat_rps / seq_rps, 2)
            if B >= 4 and bat_rps > seq_rps:
                wins_at_4plus += 1
            emit(f"serve_{name}_b{B}", 1e6 / bat_rps,
                 seq_rps=round(seq_rps, 1), batch_rps=round(bat_rps, 1),
                 speedup=speedup,
                 occupancy=round(stats.batch_occupancy(), 3))
            rows.append({
                "batch": B,
                "sequential_rps": round(seq_rps, 1),
                "batched_rps": round(bat_rps, 1),
                "speedup": speedup,
                "sequential_p50_ms": seq_p50,
                "sequential_p99_ms": seq_p99,
                "batched_p50_ms": round(stats.p50_s() * 1e3, 3),
                "batched_p99_ms": round(stats.p99_s() * 1e3, 3),
                "batch_occupancy": round(stats.batch_occupancy(), 4),
                "coalesce_ratio": round(stats.coalesce_ratio(), 4),
            })
        report["templates"][name] = rows

    # compile-cache proof: the whole run compiled exactly one batched
    # executable per (template, bucket) -- count the ("batch", bucket)
    # cache entries against the distinct buckets the batch sizes hit
    batch_keys = [k for k in ctx.compile_cache._entries
                  if isinstance(k[-1], tuple) and k[-1][0] == "batch"]
    buckets = sorted({ENG.batch_bucket(b) for b in BATCHES})
    expected = len(TEMPLATES) * len(buckets)
    report["compile_proof"] = {
        "batch_executables_compiled": len(batch_keys),
        "expected_template_bucket_pairs": expected,
        "one_compile_per_bucket": len(batch_keys) == expected,
        "buckets": buckets,
    }
    report["batched_beats_sequential_at_4plus"] = wins_at_4plus
    report["caches"] = ENG.cache_stats()
    emit("serve_compile_proof", 0.0,
         batch_executables=len(batch_keys), expected=expected)

    write_report(report, "BENCH_SERVE_JSON")  # opt-in artifact


if __name__ == "__main__":
    from benchmarks.common import entry
    entry(run)
