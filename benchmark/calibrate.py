#!/usr/bin/env python3
"""Readings that limits are set from: for a list of seeds, in one process,
what the program gives against the plain reference and what the control
gives (the reference one precision below the configuration's, put in the
program's place). Not part of a benchmark run.

  python3 benchmark/calibrate.py --workload <name> --seeds 1,2,3 [--seconds s]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    from benchmark import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.seed, args.trace = 0, 0
    cell, ctx = run.start(args)
    cell.kind.calibrate(ctx, [int(s) for s in args.seeds.split(",")])


if __name__ == "__main__":
    main()
