#!/usr/bin/env python3
"""The control and the faults, at a cell's own size, on the chip.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 \
        [--faults skip_tx,answer_altered] [--seconds 1]

For every seed: one clean run of the whole harness with a short window
(its numbers are the lower readings), then one run per fault with the
timed path broken underneath (``benchlib/faults.py``; ``skip_tx`` and
``silent_alter`` are the controls).  One JSON line per run; exit 0 only if every clean run is
correct and every faulted run is not.  All in one process, so set-up's
compiles are paid once.  The benchmark's own runs never come here.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import faults, harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=",".join(faults.FAULTS))
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearsal: skip the look for a chip")
    args = ap.parse_args(argv)
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault in [None] + args.faults.split(","):
            run_args = argparse.Namespace(
                workload=args.workload, seed=seed, seconds=args.seconds,
                trace=0)
            try:
                with faults.planted(fault):
                    res = harness.run_cell(run_args, time.monotonic(),
                                           require_tpu=not args.cpu)
                row = {"correct": res["correct"],
                       "failed": res["failed"],
                       "attempted": res["attempted"],
                       "compared": res["compared"]}
            except SystemExit as exc:  # warm-up gave up: no number
                row = {"correct": False, "gave_up": str(exc)}
            good = row["correct"] is (fault is None)
            ok &= good
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "fault": fault, "as_expected": good,
                              **row}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
