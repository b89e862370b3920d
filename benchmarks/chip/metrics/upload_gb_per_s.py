"""Bytes of every ``upload`` span over their seconds, in GB/s: a layer's
payload parsed on the host and copied onto the device, ended by
``block_until_ready`` of the uploaded arrays (program span, over the calls
that miss the profiled sub-window)."""
from chipbench import spans


def read(run):
    xs = spans.quiet(run, "upload")
    t = sum(s.dur_s for s in xs) if xs else 0.0
    return sum(s.args["bytes"] for s in xs) / t / 1e9 if t > 0 else None
