"""Median time to first token: from each request's due time to the return
of the serve() call that carried it (host clock, every request due in the
window)."""
from chipbench.stats import nearest_rank


def read(run):
    xs = [1e3 * (r["end"] - r["due"]) for r in run.requests
          if r["served"] is not None]
    return nearest_rank(xs, 50) if xs else None
