"""Median ``decode_step`` span on the ``engine/wall`` track: one
``ContinuousBatcher.step``, from dispatch to the host's argmax and slot
bookkeeping (program span, over the calls that miss the profiled
sub-window)."""
from chipbench import spans
from chipbench.stats import nearest_rank


def read(run):
    xs = spans.quiet(run, "decode_step")
    return 1e3 * nearest_rank([s.dur_s for s in xs], 50) if xs else None
