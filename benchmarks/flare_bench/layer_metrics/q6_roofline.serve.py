"""Percent of the HBM roofline reached by one batched q6 dispatch of the
server (``jit_fn`` is the name XLA gives the template's program)."""
from benchmarks.flare_bench.readers import dispatch_roofline


def read(run):
    return dispatch_roofline(run, "q6", "jit_fn")
