"""Percent of the traced window with no operation on the device."""
from benchmarks.flare_bench.readers import idle_share as read  # noqa: F401
