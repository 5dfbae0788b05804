"""Seconds per stream in the join queries (q3, q5, q10, q14, q19),
each call timed on the host until its rows were on the host."""
from benchmarks.flare_bench.readers import measured


def read(run):
    return measured(run, "join_s")
