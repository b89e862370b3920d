"""Decode FLOPs (every weight once per token, attention over the live
context, the head) of every token decoded after the first, over the busy
seconds times the chip's bf16 peak, in %.  Busy is the union of the
serve() calls.  Host clock, over the calls that miss the profiled
sub-window."""
from chipbench import stats


def read(run):
    calls = run.quiet_calls()
    if not calls:
        return None
    work = 0.0
    for r in run.requests:
        if r["served"] is None or r["call"] not in calls:
            continue
        prompt = r["matched"] + r["suffix"]
        work += sum(run.cell.arch.decode_flops(run.conf, prompt + i + 1)
                    for i in range(1, len(r["served"])))
    busy = stats.total(run.calls[i][:2] for i in calls)
    return 100.0 * work / (busy * run.peaks["bf16_flops_per_s"])
