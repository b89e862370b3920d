"""Median wait from a request's due time to the start of the serve() call
that carried it: the open loop's backlog behind the call in flight (host
clock, over the requests that miss the profiled sub-window)."""
from chipbench.stats import nearest_rank


def read(run):
    xs = [1e3 * (r["start"] - r["due"]) for r in run.quiet_requests()]
    return nearest_rank(xs, 50) if xs else None
