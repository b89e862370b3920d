"""Readings for a cell's correctness limits: the program's compared numbers
and the fp8 control's in the program's place, or a planted fault's, on
many seeds in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 ... [--fault altered_token|state_unchanged]

Per seed: weights and documents from that seed, the cell's warm-up, a
window of ``--seconds`` at the cell's own load, then ``harness.check``
over the same sample a run compares, at the limits in
``limits/<cell>.json``.  Prints one JSON line per seed: the program's
checks and whether they pass (``program``, ``program_correct``) and,
without ``--fault``, the control's (``control``, ``control_correct``,
which has to be false).  With ``--fault`` the fault is planted in the
program before anything is built, and ``program_correct`` has to be
false.  Needs a TPU.
"""
import argparse
import gc
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from chipbench import harness
    from chipbench.faults import FAULTS
    from chipbench.layout import Layout
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    use_compile_cache()
    if args.fault is not None:
        FAULTS[args.fault](setattr)
    layout = Layout(ROOT)
    cell = layout.cell(args.workload)
    limits = harness.read_limits(layout, args.workload)
    for seed in args.seeds:
        traffic, system = harness.prepare(cell, seed)
        records, _, _ = harness.drive(system, traffic, args.seconds)
        params = system.params
        del system
        gc.collect()
        failed, program, control = harness.check(
            params, cell, traffic, records, limits, seed,
            control=args.fault is None)
        line = {"seed": seed, "fault": args.fault, "failed": failed,
                "requests": len(records), "program": program,
                "program_correct": failed == 0
                and harness.compared_ok(program)}
        if control is not None:
            line.update(control=control,
                        control_correct=harness.compared_ok(control))
        print(json.dumps(line), flush=True)
        del params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
