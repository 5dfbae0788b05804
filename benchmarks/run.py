"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src:. python -m benchmarks.run [--only q6,join,...] [--sf 0.05]

Prints ``name,us_per_call,derived`` CSV.  Modules:

    q6        Fig 4/5   Q6 across engines + direct-vs-preload + kernel
    join      Fig 6     join strategy comparison
    tpch      Fig 9     TPC-H suite across engines + compile times
    loading   Table 1   CSV generic/compiled + flarecol (+projection)
    scaling   Fig 11/12 mesh-parallel relational scaling (device subsets)
    ml        Fig 8/13/14  heterogeneous ETL+ML fused vs staged
    roofline  (g)       roofline terms from the dry-run artifacts

Each module runs in a process of its own, one after the other, and
this parent never imports JAX: an accelerator belongs to one process
at a time, so a parent that held it would starve its children.  The
children share JAX's persistent compilation cache
(:mod:`repro.persist.xla_cache`).
"""
from __future__ import annotations

import argparse
import importlib
import os
import subprocess
import sys

MODULES = ["q6", "join", "tpch", "loading", "scaling", "ml", "roofline"]


def _module_path(name: str) -> str:
    return ("benchmarks.roofline" if name == "roofline"
            else f"benchmarks.bench_{name}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(MODULES))
    ap.add_argument("--sf", type=float, default=None,
                    help="TPC-H scale factor (default 0.05)")
    ap.add_argument("--module", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.module:
        run_module(args.module)
        return
    env = dict(os.environ)
    if args.sf is not None:
        env["BENCH_SF"] = str(args.sf)

    names = (args.only.split(",") if args.only else MODULES)
    print("name,us_per_call,derived", flush=True)
    failures = 0
    for name in names:
        rc = subprocess.run([sys.executable, "-m", "benchmarks.run",
                             "--module", name], env=env).returncode
        if rc != 0:
            failures += 1
            print(f"{name},-1.0,error=1", flush=True)
    if failures:
        sys.exit(1)


def run_module(name: str) -> None:
    from benchmarks.common import entry
    entry(importlib.import_module(_module_path(name)).run)


if __name__ == "__main__":
    main()
