"""Readings that the check limits are set from: the program's numbers and
the control's (the reference in float8, the precision below the bfloat16
the configuration states, put in the program's place) on many seeds, in
one process on the chip; or, with `--fault`, the numbers of the program
with that fault planted (`bench/faults.py`).

    python bench/limits.py --workload <name> --seeds 11,12,13 --seconds 5 \
        [--control fp8] [--fault half_batch]

Each seed runs the cell as `run.py` does, with a short window, and judges
it against the cell's limits; with `--control` the same readings are
judged once more with the control in the program's place. Prints one JSON
line per seed and a last line with, per number, the largest program
reading (the lower end of the limit's range; with a fault planted, the
smallest is the fault's reading) and the smallest control reading (the
upper end). Not run by the benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                   "src")]

from bench import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    if args.fault:
        from bench import faults
        for obj, attr, value in faults.patches(args.fault):
            setattr(obj, attr, value)
    lo, lo_min, hi, watch = {}, {}, {}, None
    for seed in [int(x) for x in args.seeds.split(",")]:
        cell, label = run.prepare(args.workload, seed, args.seconds, 0,
                                  t_start=time.perf_counter(), watch=watch)
        watch = (cell.routes, cell.clock)
        out, checks = run.run_cell(cell, label)
        line = {"seed": seed, "correct": out["correct"], "checks": checks,
                "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        for k, v in checks.items():
            lo[k] = max(lo.get(k, 0.0), v)
            lo_min[k] = min(lo_min.get(k, float("inf")), v)
        if args.control:
            cell.control = args.control
            ok, _, ctrl = run.judge(cell, run.driver(cell.mix),
                                    cell.readings)
            line["control"] = {"correct": ok, "checks": ctrl}
            for k, v in ctrl.items():
                hi[k] = min(hi.get(k, float("inf")), v)
        print(json.dumps(line), flush=True)
        del cell, out
        gc.collect()
    print(json.dumps({"fault": args.fault, "program_max": lo,
                      "program_min": lo_min, "control_min": hi}), flush=True)


if __name__ == "__main__":
    main()
