"""Seconds of ``ctx.preload()``: placing every column on the device
and building the join indexes, on the host clock."""


def read(run):
    return run["load_s"]
