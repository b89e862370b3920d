"""Model FLOPs of the served prefills (each suffix through every layer,
causal attention over document + suffix, the last token's head) over the
busy seconds times the chip's bf16 peak, in %.  Busy is the union of the
serve() calls, so the offered load does not set it.  Host clock, over the
calls that miss the profiled sub-window."""
from chipbench import stats


def read(run):
    calls = run.quiet_calls()
    if not calls:
        return None
    work = sum(run.cell.arch.prefill_flops(run.conf, r["suffix"],
                                           r["matched"])
               for r in run.requests
               if r["served"] is not None and r["call"] in calls)
    busy = stats.total(run.calls[i][:2] for i in calls)
    return 100.0 * work / (busy * run.peaks["bf16_flops_per_s"])
