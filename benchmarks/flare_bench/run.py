"""Run one benchmark cell once and print its result as the last line.

    python3 benchmarks/flare_bench/run.py --workload tpch-sf1.power \\
        --seed 7 --seconds 30 --trace 0

Generates the cell's TPC-H tables from ``--seed``, loads them onto the
chip, compiles and warms the cell's queries (set-up), drives the cell's
traffic for ``--seconds``, checks the answers against the benchmark's
own reference, and prints one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1``
the per-layer ones read from a profiler trace), ``device``, and last
``checks``, each compared number beside its limit.  Exits 2, printing
no result, when JAX finds no TPU or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from benchmarks.flare_bench import harness
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START)
    except harness.NoChip as err:
        print(f"flare_bench: {err}; nothing was run", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
