"""Percent of the HBM roofline reached by one q6 in the power stream."""
from benchmarks.flare_bench.readers import stream_roofline


def read(run):
    return stream_roofline(run, "q6")
