"""95th percentile of the server's admission-queue wait in the window
(``ServeStats.queue_s``: submit to batch dispatch), in milliseconds."""
from benchmarks.flare_bench.readers import measured


def read(run):
    return measured(run, "queue_wait_p95_ms")
