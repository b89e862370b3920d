"""Median over requests of the ``commit`` spans on each request's
``<req>/wall`` track: the cache's copy to the host, its encoding into chunk
objects and ``Orchestrator.commit`` (program span, over the calls that
miss the profiled sub-window)."""
from chipbench import spans
from chipbench.stats import nearest_rank


def read(run):
    per = spans.per_request(run, "commit")
    return 1e3 * nearest_rank(list(per.values()), 50) if per else None
