#!/usr/bin/env python3
"""Readings that the limits in ``bench/limits`` are set from.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1 2 3 ... [--control-seeds 4 5 6 ...]

In one process, for each seed, a run of the cell as ``bench/run.py`` makes
it: weights from the seed, the timed path at the cell's load for
``--seconds``, then the plain reference over a sample of the served tokens.
Each ``--control-seeds`` seed runs the control the same way: the program
with its own 8-bit path (the ``w8`` policy) in place of the configuration's
12 bits.  Prints one JSON line per run with the numbers compared.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run

CONTROL = "w8"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    todo = [(s, None) for s in args.seeds] + [(s, CONTROL) for s in args.control_seeds]
    for seed, quant in todo:
        one = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0)
        try:
            res = run.run(one, quant=quant)
        except run.NoChip as e:
            print(e, file=sys.stderr)
            return 3
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "run": "control" if quant else "program",
                          "quant": quant or "cell", **res["numbers"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
