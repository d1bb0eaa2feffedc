"""One command runs one cell once:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that owns the cell's chips: builds the model through the
program's normal path with weights made on the device from ``--seed``,
checks what the timed path produces against the plain reference, warms
exactly the cell's shapes, measures for ``--seconds`` and prints one JSON
result line last.  Without a TPU (or for a device that is not in
benchmark/peaks.py) it exits 2 and prints no result.  ``--check`` is the
CPU pass at tiny sizes: correctness and counts, never a time.
"""

import time

T_START = time.perf_counter()

import argparse    # noqa: E402
import json        # noqa: E402
import os          # noqa: E402
import sys         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--check", action="store_true",
                    help="CPU pass at tiny sizes: outputs and counts only")
    args = ap.parse_args(argv)
    from benchmark import harness

    seconds = args.seconds
    if seconds is None:
        seconds = harness.load_benchmark()["run_seconds"]
    result = harness.run_cell(args.workload, args.seed, seconds, args.trace,
                              check=args.check, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
