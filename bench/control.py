#!/usr/bin/env python3
"""Runs that set and justify a cell's correctness limits; the benchmark's
own runs never run this.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        [--control fp8|half_positions] [--seconds 10]

Each seed is one whole run of the cell through ``harness.run_cell``, in
which the driver puts the control in the program's place in the check
that decides ``correct``: ``fp8`` is the plain reference computed in
float8 e4m3, the precision below the configurations' bfloat16;
``half_positions`` (training) is the reference with half of the
positions left out of the loss, a planted fault.  The check holds it
against the cell's own limits, so a control that the limits catch prints
``"correct": false``.  The program's own numbers of the same run are kept
under ``notes.program``.  A step that leaves the state unchanged reads 1
on the training gaps by their measure and needs no run.

One process runs every seed.  Each seed's result line, with ``seed`` and
``control`` added, is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="fp8",
                    choices=("fp8", "half_positions"))
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    harness.use_cache_dir()
    harness.cache_every_program()
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.parse(["--workload", args.workload, "--seed",
                             str(seed), "--seconds", str(args.seconds)])
        line = harness.run_cell(run, t_start=time.perf_counter(),
                                control=args.control)
        print(json.dumps({"seed": seed, "control": args.control, **line}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
