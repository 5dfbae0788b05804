"""95th percentile of the ``serve.wait`` span over the requests traced,
in milliseconds: from the reader's ``result()`` to the request's device
output being ready (the readiness polls and their sleeps)."""
from benchmarks.flare_bench import program_trace as PT


def read(run):
    return PT.span_p95_ms(PT.trace(), "serve.wait")
