#!/usr/bin/env python3
"""Readings from which a cell's ``correct`` limits are set.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed, in one process: set-up, a window of ``--seconds`` at the
cell's own load, and the reference over the run's served tokens and pool
codes, reading the numbers compared for the program, for the float8
control (the reference in the next precision below bfloat16, put in the
program's place) and for a token altered where it is produced (planted in
the reference), and the verdict of each by the cell's limits.  One JSON
line per seed on standard output.  The benchmark's own runs do not run
the control.  ``--cpu`` runs a cell of a CPU-sized configuration here,
without the chip's kernel check.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.config:
        cell = {"name": args.workload, "config": args.config,
                "traffic": args.traffic, "chips": 1}
        bench = {"workloads": [cell], "end_to_end": [], "per_layer": []}
    else:
        bench, cell = run.load_cell(args.workload)
    import jax
    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("bench/calibrate.py: no TPU", file=sys.stderr)
        return 3
    for seed in args.seeds:
        t = time.perf_counter()
        res = run.run_cell(bench, cell, seed, args.seconds, False,
                           on_chip=not args.cpu, control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "readings": res["readings"],
                          "attempted": res["attempted"],
                          "metrics": res["metrics"],
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
