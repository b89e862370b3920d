"""The chip benchmark's command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints one JSON object as the last line of standard output.  With
``--trace 0`` its metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the program's wall spans and
a profiler trace of a few seconds inside the window.  Exits 1, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chipbench: the program is not in {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from chipbench.harness import run

    out = run(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
              t_start=T_START)
    if out is None:
        return 1
    for name, c in out["checks"].items():
        print(f"[check] {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
