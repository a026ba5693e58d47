#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this machine holds.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (process start to the window: loading, weights made on the device
from the seed, compiling or reading the compile cache, warm-up) is
``setup_s``.  Then the window runs for ``--seconds``; with ``--trace 1``
the profiler records it and the per-layer metrics are read from the
trace, otherwise the end-to-end metrics are reported.  After the window
the program's state is freed and a plain reference decides ``correct``.
The last line of standard output is the result as one JSON object; the
last lines of standard error give each number compared beside its
limit.  Without a TPU, or with fewer chips than the cell asks for, it
exits with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def process_start() -> float:
    """This process's start on ``time.time``'s clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


T_START = process_start()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    from harness import cell as cell_mod, device
    from harness.spec import load_cell
    cell = load_cell(args.workload)
    try:
        devices = device.require(cell.chips)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    cell_mod.enable_compile_cache()
    out = cell_mod.run(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=T_START, devices=devices)
    for line in out["stderr"]:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["line"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
