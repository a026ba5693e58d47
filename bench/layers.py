#!/usr/bin/env python3
"""Run one cell's window under the profiler and print the device time of
each layer of the program.  Not part of a benchmark run.

  python3 bench/layers.py --workload <name> --seed <n> --seconds <s>

Set-up and the window are the cell's own, as ``bench/run.py`` runs them:
its job, weights made from the seed, the warm-up steps.  The window is
traced, and each device operation is given the layer of the program
that traced it (``harness/scopes.py``, from the ``op_name`` metadata of
the compiled programs).  Standard error gives the scopes by time; the
last line of standard output is one JSON object: the device, the
window's steps and end-to-end metrics (traced, so slowed by the
profiler), busy and window seconds, device self seconds per scope, and,
for a training cell, the required-FLOPs share of the bf16 peak of each
of ``MFU_LAYERS`` over its own device seconds and the ``stack`` and
``unscoped`` shares of busy time.  Nothing is compared against a
reference.  Without a TPU it exits with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

# the layers whose own share of the peak is printed: those that hold the
# step's matmuls in Phase III
MFU_LAYERS = ("attention", "experts", "shared_expert", "head")


def summary(window, scope_s, busy_s, work, peaks, chips):
    """Per-layer shares of one traced training window, in %."""
    from harness import scopes
    from harness.spec import load_module
    layers = scopes.by_layer(scope_s)
    flops = load_module("flops", "moe_lm_layers").layer_flops(
        work["arch"], work["batch"], work["seq"])
    return {
        "mfu": {name: 100.0 * flops[name] * window["steps"]
                / (layers[name] * peaks["bf16_flops"] * chips)
                for name in MFU_LAYERS if layers.get(name)},
        "stack_share": 100.0 * layers.get("stack", 0.0) / busy_s,
        "unscoped_share": 100.0 * layers.get(scopes.UNSCOPED, 0.0) / busy_s,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from harness import cell as cell_mod, device
    from harness.spec import load_cell
    cell = load_cell(args.workload)
    try:
        devices = device.require(cell.chips)
    except device.NoChip as e:
        print(f"layers: {e}", file=sys.stderr)
        return 2
    cell_mod.enable_compile_cache()

    import jax
    from harness import scopes, trace
    from harness.spans import Spans
    from harness.spec import load_module
    job = load_module("jobs", cell.traffic["job"]).Job(cell, args.seed,
                                                        Spans())
    tdir = tempfile.mkdtemp(prefix="bench_layers_")
    try:
        with trace.recording(tdir):
            with job.spans("window"):
                win = job.window(args.seconds)
        pd = jax.profiler.ProfileData.from_file(trace.xplane_file(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    n = len(devices)
    red = trace.reduce_profile(pd, "bench.window", n,
                               trace.op_labels(job.programs))
    scope_s = scopes.seconds(pd, "bench.window", n, job.programs)
    line = {"workload": cell.name, "seed": args.seed,
            "device": device.describe(devices), "steps": win.get("steps"),
            "end_to_end": win["end_to_end"], "busy_s": red["busy_s"],
            "window_s": red["window_s"], "scopes": scope_s,
            "device_ops": red["device_ops"]}
    if "train_tokens_per_s" in win["end_to_end"]:
        line.update(summary(win, scope_s, red["busy_s"], job.work(),
                            device.peaks(devices[0].device_kind), n))
    print("scopes: " + " ".join(
        f"{k} {v:.6f}" for k, v in sorted(scope_s.items(),
                                          key=lambda kv: -kv[1])),
        file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
