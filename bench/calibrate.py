#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` on many seeds in one process,
to set their limits: the program's sound runs, the control (the plain
reference in the program's place, its products rounded to float8), and
the program with a fault planted.  Not part of a benchmark run.

  python3 bench/calibrate.py --workload qwen2moe.tune --seeds 1,2,3 \\
      [--control] [--fault half_batch] [--out readings.jsonl]

Each seed prints one JSON line: the side, the seed and the three gaps
with the leaf or step where each is worst.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

FAULTS = ("unchanged", "half_batch")   # faults.NAMES, without importing JAX
NO_LIMITS = {"loss_gap": math.inf, "grad_gap": math.inf,
             "change_gap": math.inf}


def readings(cell, seed, *, fault=None, control=False):
    """[(side, checks)] for one seed."""
    import faults
    from harness import compare
    from harness.spans import Spans
    from harness.spec import load_module
    from reference import moe_lm

    job_mod = load_module("jobs", cell.traffic["job"])
    with faults.planted(fault) if fault else contextlib.nullcontext():
        job = job_mod.Job(cell, seed, Spans())
    job.free()
    ref = job.reference()
    out = [(fault or "program",
            compare.training(job.readings, ref, NO_LIMITS))]
    if control:
        ctrl = job.reference(q=moe_lm.fp8)
        out.append(("control", compare.training(ctrl, ref, NO_LIMITS)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None, choices=FAULTS)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from harness import cell as cell_mod, device
    from harness.spec import load_cell
    cell = load_cell(args.workload)
    device.require(cell.chips)
    cell_mod.enable_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        for side, checks in readings(cell, seed, fault=args.fault,
                                     control=args.control):
            rec = {"workload": args.workload, "side": side, "seed": seed,
                   **{c["name"]: c["value"] for c in checks},
                   "where": {c["name"]: c["where"] for c in checks}}
            line = json.dumps(rec)
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
