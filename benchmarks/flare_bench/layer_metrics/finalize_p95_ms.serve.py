"""95th percentile of the ``serve.finalize`` span over the requests
traced, in milliseconds: from the device output being ready to the
request's rows on the host (its slices, transfers and ``Result``)."""
from benchmarks.flare_bench import program_trace as PT


def read(run):
    return PT.span_p95_ms(PT.trace(), "serve.finalize")
