"""Median over requests of the ``fetch`` spans on each request's
``<req>/wall`` track: ``Orchestrator.fetch`` with its descriptor, gateway,
aggregation and object-store range reads (program span, over the calls
that miss the profiled sub-window)."""
from chipbench import spans
from chipbench.stats import nearest_rank


def read(run):
    per = spans.per_request(run, "fetch")
    return 1e3 * nearest_rank(list(per.values()), 50) if per else None
