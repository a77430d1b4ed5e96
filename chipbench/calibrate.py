"""Readings for setting the limits of `correct`, many seeds in one
process (set-up is most of a run's cost):

    python -m chipbench.calibrate --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 [--seconds 8] [--rehearse]

Prints one JSON line per seed and side (``program``, the control, each
fault) with the numbers `check` compares; the limits go between the
largest ``program`` reading and the smallest of the others, as PERF.md
section 2 records. The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import harness
from . import manifest as mf


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m chipbench.calibrate")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    cell = mf.Cell(mf.load(), args.workload)
    run = harness.Run(cell, seeds[0], args.seconds, False, args.rehearse)
    try:
        harness.device_gate(run)
    except harness.Refused as e:
        print(f"chipbench.calibrate: {e}", file=sys.stderr)
        return 1
    harness.enable_cache(run)
    runner = cell.runner().Runner(run)
    try:
        runner.setup()
        for row in runner.calibrate(seeds, control):
            print(json.dumps(row), flush=True)
    finally:
        runner.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
