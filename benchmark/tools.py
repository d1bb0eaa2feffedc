"""The two measurements made once, when a cell is defined, and not in the
benchmark's own runs:

    python3 benchmark/tools.py readings --workload W --seeds 1,2,3
        per seed, in one process: what a sound run of the program reads
        for each number `correct` compares, and what the control reads
        (the plain reference in the configuration's ``control_precision``);
        the limits in the configuration's file are set between the two.
    python3 benchmark/tools.py sweep --workload W --rates 1,2,4 --seconds 20
        the knee of a serving cell: each rate in turn on one engine.

Both need the chip, like every timed path; ``--check`` runs them at the
tiny sizes on the CPU."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    from benchmark import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",") if x]
    ctx = harness.open_cell(args.workload, seeds[0], args.seconds, 0,
                            check=args.check)
    gen = harness.load_module("generators", ctx.traffic["generator"], ROOT)
    kinds = [ctx.cfg["control_precision"]]
    if args.what == "sweep":
        rows = gen.sweep(ctx, [float(r) for r in args.rates.split(",")],
                         args.seconds)
    else:
        rows = gen.readings(ctx, seeds, args.seconds, kinds)
    print(json.dumps({"what": args.what, "workload": args.workload,
                      "control": kinds, "device": ctx.device, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
