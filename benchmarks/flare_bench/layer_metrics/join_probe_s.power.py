"""Device seconds per traced stream under ``flare:join.probe`` (combined
keys, ``searchsorted``, clip, match and validity masks) in the stream's
join queries (``join_queries`` of traffic/power.json)."""
import json
import os

from benchmarks.flare_bench import program_trace as PT

with open(os.path.join(PT.HERE, "traffic", "power.json")) as f:
    MIX = json.load(f)


def read(run):
    return PT.stream_scope_s(run, PT.trace(), "flare:join.probe",
                             MIX["join_queries"], MIX["queries"][0])
