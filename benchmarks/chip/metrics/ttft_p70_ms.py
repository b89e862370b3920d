"""70th percentile (nearest rank) of the time to first token, the same
samples as ``ttft_p50_ms``: the highest percentile with ten samples beyond
it when a window holds about 36 requests."""
from chipbench.stats import nearest_rank


def read(run):
    xs = [1e3 * (r["end"] - r["due"]) for r in run.requests
          if r["served"] is not None]
    return nearest_rank(xs, 70) if xs else None
