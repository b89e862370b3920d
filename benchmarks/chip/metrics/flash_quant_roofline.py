"""``flash_attention_quant``'s share of its roofline, in %: the least time
the chip could take for the kernel calls of the serve() calls that lie
wholly inside the trace (per call the larger of FLOPs over the bf16 peak
and bytes over HBM bandwidth) over the kernel's device time in those calls.
Which bound binds is logged."""
import sys

from chipbench import flops, xtrace

KERNEL = "flash_attention_quant_op"


def read(run):
    if run.trace is None:
        return None
    calls = set(run.profiled_calls())
    windows = [(run.calls[i][0] + run.offset, run.calls[i][1] + run.offset)
               for i in calls]
    t, n = xtrace.op_time(run.trace, lambda name: KERNEL in name, windows)
    if n == 0:
        return None
    serving = run.conf["serving"]
    L = int(run.conf["num_hidden_layers"])
    least, compute_bound, n_calls = 0.0, 0, 0
    for r in run.requests:
        if r["call"] not in calls or r["served"] is None:
            continue
        f, b = flops.flash_attention_quant(
            run.conf, queries=r["suffix"], keys=r["matched"],
            bits=int(serving["bits"]), group=int(serving["group"]),
            chunk_tokens=int(serving["chunk_tokens"]))
        tf, tb = f / run.peaks["bf16_flops_per_s"], \
            b / run.peaks["hbm_bytes_per_s"]
        least += L * max(tf, tb)
        compute_bound += L * (tf >= tb)
        n_calls += L
    if n != n_calls:
        print(f"[flash_quant_roofline] {n} kernel events for {n_calls} "
              f"layer steps in the traced calls", file=sys.stderr)
        return None
    print(f"[flash_quant_roofline] calls={n} kernel_s={t} least_s={least} "
          f"compute_bound_calls={compute_bound}", file=sys.stderr)
    return 100.0 * least / t
