"""95th percentile of the server's ``serve.dispatch`` span over the
batches traced, in milliseconds: stacking the bindings, marshalling the
arguments, uploading the parameters and the executable call."""
from benchmarks.flare_bench import program_trace as PT


def read(run):
    return PT.span_p95_ms(PT.trace(), "serve.dispatch")
