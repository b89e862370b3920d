"""Median ``slice`` span on the ``<req>/wall`` tracks: one layer's weights
sliced out of the stacked parameters (``ModelRunner.layer_params``), ended
by ``block_until_ready`` (program span, over the calls that miss the
profiled sub-window)."""
from chipbench import spans
from chipbench.stats import nearest_rank


def read(run):
    xs = spans.quiet(run, "slice")
    return 1e3 * nearest_rank([s.dur_s for s in xs], 50) if xs else None
