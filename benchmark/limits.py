"""Readings for the limits of the comparison that decides `correct`:

    python3 benchmark/limits.py --workload <name> --seeds 1,2,3 --seconds <s> --out <file>

For each seed, in one process, it drives the cell as a benchmark run does
and prints what the program read against the reference, and beside it what
the control (the reference in fp8) and the planted faults read.  The
benchmark's own runs never run the control.  PERF.md holds the readings
each limit was set from."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args(argv)
    with open(args.out, "a") as out:
        for seed in (int(s) for s in args.seeds.split(",")):
            line = bench_run.run_cell(
                args.workload, seed, args.seconds, trace=False,
                t_start=time.perf_counter(), control=not args.no_control)
            rec = {"workload": args.workload, "seed": seed,
                   "correct": line["correct"], "compared": line["compared"],
                   "control": line.get("control"),
                   "detail": line.get("detail"),
                   "metrics": line["metrics"]}
            out.write(json.dumps(rec) + "\n")
            out.flush()
            print(json.dumps(rec), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
