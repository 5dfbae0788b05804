"""The control of the check: the reference in bfloat16, put in the
program's place, must come out as not correct.

The configurations state float32 arithmetic; the precision below it
is bfloat16, the step that would tempt a later change (columns held in
bfloat16).  For each seed this generates the cell's tables at the
cell's own size, answers the cell's queries with ``Reference(tables,
BF16)``, shapes each answer as the query returns it (``present``), and
compares it with the float64 reference exactly as ``harness.run_cell``
compares the program's answers.  The benchmark's own runs never run
it.

    python3 benchmarks/flare_bench/control.py --workload tpch-sf10.scan \\
        --seeds 11 12 13
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compared_set(mix: Dict[str, Any], reference, rng
                 ) -> List[Tuple[str, Dict[str, Any]]]:
    """The (query, binding) pairs a run of the mix compares."""
    from benchmarks.flare_bench import traffic as TR
    if mix["loop"] == "stream":
        return [(q, reference.q22_binding() if q == "q22" else {})
                for q in mix["queries"]]
    pool = TR.BINDINGS[mix["template"]]()
    pick = rng.choice(len(pool), min(int(mix["check_bindings"]), len(pool)),
                      replace=False)
    return [(mix["template"], pool[i]) for i in sorted(pick)]


def readings(workload: str, seed: int, config: Dict[str, Any] = None,
             mix: Dict[str, Any] = None) -> Dict[str, float]:
    """The control's compared numbers for one seed."""
    from benchmarks.flare_bench import compare as C
    from benchmarks.flare_bench import harness as H
    from benchmarks.flare_bench.reference import BF16, Reference, present
    from benchmarks.flare_bench.tables import generate
    _, _, cell_config, cell_mix = H.cell_files(workload)
    config = config or cell_config
    mix = mix or cell_mix
    raw = generate(config, seed)
    ref, ctl = Reference(raw), Reference(raw, BF16)
    results = []
    for query, params in compared_set(mix, ref, np.random.default_rng(seed)):
        want = ref.run(query, params)
        results.append(C.compare(query, present(query,
                                                ctl.run(query, params)),
                                 want))
    return C.merge(results)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        t = time.perf_counter()
        out = readings(args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": out,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
