"""Cold-start latency: what the persistent artifact store buys a restart.

Flare's deployment story assumes long-lived servers, but every server
restarts; this benchmark measures the first-prepared-query latency a
fresh process pays under three regimes:

- ``cold``        -- empty ``FLARE_CACHE_DIR``: trace + XLA compile.
- ``warm_disk``   -- fresh process, store populated by a previous
  process: executables deserialize from disk (repro.persist), no XLA.
- ``warm_memory`` -- same process, second compile of the same template:
  in-memory ``CompileCache`` hit, the steady-state floor.

cold and warm_disk each run in their own subprocess (a restart cannot be
simulated in-process: jit caches and the XLA compilation cache are
process-global), sharing one ``FLARE_CACHE_DIR``.  Per template we
report first-query latency (compile + first execute) and the store
telemetry that attributes it -- ``warm_disk`` asserts zero executable
compiles.  Results go to CSV rows (harness contract) and a JSON
artifact at ``$BENCH_COLDSTART_JSON`` (default ``bench_coldstart.json``)
for CI upload.  DESIGN.md section 12 describes the store.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

SF = float(os.environ.get("BENCH_SF", "0.01"))
TEMPLATE_NAMES = tuple(
    os.environ.get("BENCH_COLDSTART_TEMPLATES", "q6,q19").split(","))


def _child(template_names) -> None:
    """One process's measurement: compile + first execution per template,
    twice (the second pass is the warm_memory figure), plus store stats.
    Prints one JSON object to stdout.

    JAX's own persistent compilation cache is off here: a warm one
    would turn "cold" into a cache read, and would serve "warm_disk"
    whatever the Flare store missed."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    import jax.numpy as jnp

    from repro.core.dataframe import FlareContext
    from repro.persist import store as PS
    from repro.relational import queries as Q

    ctx = FlareContext()
    Q.register_tpch(ctx, sf=SF)
    # One throwaway dispatch so process-global runtime init (backend
    # bring-up, first transfer) is not billed to the first template.
    jnp.ones(8).sum().block_until_ready()
    out = {"templates": {}, "store": None}
    for name in template_names:
        binding = Q.random_bindings(name, 1, seed=7)[0]
        t0 = time.perf_counter()
        compiled = Q.TEMPLATES[name](ctx).lower(engine="compiled").compile()
        compiled.collect(**binding)
        first_us = (time.perf_counter() - t0) * 1e6
        # warm_memory: a fresh Lowered against the same context hits the
        # in-memory CompileCache before the store is even consulted.
        t0 = time.perf_counter()
        again = Q.TEMPLATES[name](ctx).lower(engine="compiled").compile()
        again.collect(**binding)
        mem_us = (time.perf_counter() - t0) * 1e6
        out["templates"][name] = {
            "first_us": round(first_us, 1),
            "warm_memory_us": round(mem_us, 1),
            "disk_hit": compiled.stats.disk_hit,
            "compile_s": round(compiled.stats.compile_s, 6),
        }
    out["store"] = PS.live_store_stats()
    json.dump(out, sys.stdout)


def _spawn(cache_dir: str) -> dict:
    env = dict(os.environ, FLARE_CACHE_DIR=cache_dir,
               BENCH_SF=str(SF), PYTHONPATH=_pythonpath())
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--templates", ",".join(TEMPLATE_NAMES)],
        capture_output=True, text=True, env=env, check=False)
    if proc.returncode != 0:
        raise RuntimeError(f"child failed:\n{proc.stderr}")
    return json.loads(proc.stdout)


def _pythonpath() -> str:
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    have = os.environ.get("PYTHONPATH", "")
    return f"{src}{os.pathsep}{have}" if have else src


def run() -> dict:
    from benchmarks.common import emit, write_report

    report = {"sf": SF, "templates": {}}
    with tempfile.TemporaryDirectory(prefix="flare-coldstart-") as cache:
        cold = _spawn(cache)   # empty store: compiles, writes through
        warm = _spawn(cache)   # fresh process, populated store
        report["store_cold"] = cold["store"]
        report["store_warm"] = warm["store"]
        exec_warm = warm["store"]["exec"]
        if exec_warm["writes"] != 0 or exec_warm["hits"] == 0:
            raise AssertionError(
                f"warm-disk run recompiled: {exec_warm}")
        for name in TEMPLATE_NAMES:
            c, w = cold["templates"][name], warm["templates"][name]
            row = {
                "cold_us": c["first_us"],
                "warm_disk_us": w["first_us"],
                "warm_memory_us": w["warm_memory_us"],
                "disk_speedup": round(c["first_us"] / w["first_us"], 2),
                "disk_hit": w["disk_hit"],
            }
            report["templates"][name] = row
            emit(f"coldstart_{name}", w["first_us"], **row)
    write_report(report, "BENCH_COLDSTART_JSON",
                 default="bench_coldstart.json")
    return report


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--templates", default=",".join(TEMPLATE_NAMES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        _child(tuple(args.templates.split(",")))
    else:
        run()


if __name__ == "__main__":
    main()
