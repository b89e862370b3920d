"""Find an open-loop cell's knee: the highest offered rate whose admission
wait does not grow through the window.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 2 3 4 5

Sets the cell up once, then drives one window per rate (the mix with only
``rate_per_s`` changed) and prints one JSON line per rate: requests, TTFT
p50/p70 and the median admission wait in each third of the window.  A wait
that climbs from the first third to the last means the queue grows: the
rate is above the knee.  The cell's traffic file keeps a fixed rate; this
tool only finds it.  Needs a TPU, like ``run.py``.
"""
import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from chipbench import harness
    from chipbench.layout import Layout
    from chipbench.stats import nearest_rank
    from chipbench.traffic import Traffic
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    use_compile_cache()
    cell = Layout(ROOT).cell(args.workload)
    conf = cell.config
    base, system = harness.prepare(cell, args.seed)
    for i, rate in enumerate(args.rates):
        mix = dict(cell.traffic, rate_per_s=rate)
        traffic = Traffic(mix, conf["vocab_size"], args.seed + 1 + i)
        traffic.documents = base.documents  # the committed ones
        records, calls, _ = harness.drive(system, traffic, args.seconds)
        ttft = [1e3 * (r["end"] - r["due"]) for r in records]
        thirds = []
        for k in range(3):
            lo, hi = k * args.seconds / 3, (k + 1) * args.seconds / 3
            w = [1e3 * (r["start"] - r["due"]) for r in records
                 if lo <= r["due"] < hi]
            thirds.append(nearest_rank(w, 50) if w else None)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(records),
            "calls": len(calls),
            "ttft_p50_ms": nearest_rank(ttft, 50),
            "ttft_p70_ms": nearest_rank(ttft, 70),
            "admission_wait_p50_ms_by_third": thirds,
            "drain_s": max(b for _, b, _ in calls) - args.seconds}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
