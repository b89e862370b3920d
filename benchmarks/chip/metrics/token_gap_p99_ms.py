"""99th percentile (nearest rank) of the gaps between consecutive output
tokens of each request.  A request's first token is stamped at the end of
its ``final`` span, each later one at the end of the ``decode_step`` span
that lists it in ``req_ids`` (program span, over the calls that miss the
profiled sub-window)."""
from chipbench import spans
from chipbench.stats import nearest_rank


def read(run):
    finals, steps = spans.quiet(run, "final"), spans.quiet(run, "decode_step")
    if finals is None:
        return None
    stamps = {s.track[:-len(spans.WALL)]: [s.t1] for s in finals}
    for s in steps:
        for r in s.args["req_ids"]:
            stamps.setdefault(r, []).append(s.t1)
    gaps = [b - a for ts in stamps.values()
            for a, b in zip(sorted(ts), sorted(ts)[1:])]
    return 1e3 * nearest_rank(gaps, 99) if gaps else None
