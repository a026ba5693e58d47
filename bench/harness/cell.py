"""One run of one cell: set-up, the measured window, the trace, and the
comparison that decides ``correct``.  Prints nothing; returns the
result's line and the lines that go to standard error."""
from __future__ import annotations

import os
import shutil
import tempfile
import time
from typing import Dict

from harness import compare, device, trace
from harness.spans import Spans
from harness.spec import Cell, ROOT, load_module

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the fixed ``.jax_cache/`` of
    this checkout, for every program however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


class CompileCounter:
    """Counts backend compiles while ``armed`` (none belong in a window)."""

    def __init__(self):
        import jax
        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def run(cell: Cell, seed: int, seconds: float, traced: bool, *,
        t_start: float, devices) -> Dict:
    """``{"line": result dict, "stderr": [lines]}`` of one run.
    ``t_start`` is the process's start on ``time.time``'s clock."""
    peaks = device.peaks(devices[0].device_kind)
    job_mod = load_module("jobs", cell.traffic["job"])
    spans = Spans()
    compiles = CompileCounter()
    try:
        t_job = time.time()
        job = job_mod.Job(cell, seed, spans)
        setup_s = time.time() - t_start
        phases = [("start", t_job - t_start)] + [
            (name, t1 - t0) for name, t0, t1 in spans.done]
        tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
        compiles.armed = True
        if traced:
            with trace.recording(tdir):
                with spans("window"):
                    win = job.window(seconds)
        else:
            win = job.window(seconds)
        compiles.armed = False
        peak = device.memory_peak_bytes(devices)
        info = device.describe(devices)
        info["memory_peak_bytes"] = peak
        metrics: Dict[str, Dict] = {}
        breakdown = None
        if traced:
            red = trace.reduce(tdir, "bench.window", devices=len(devices),
                               hlo_texts=job.programs)
            shutil.rmtree(tdir, ignore_errors=True)
            info["busy_s"], info["window_s"] = red["busy_s"], red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            ctx = {"window": win, "trace": red, "work": job.work(),
                   "flops": job_mod.FLOPS, "chips": len(devices),
                   "peaks": peaks}
            for m in cell.per_layer:
                v = load_module("metrics", m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            values = dict(win["end_to_end"], setup_s=setup_s)
            for m in cell.end_to_end:
                if m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
                elif "workloads" in m:
                    raise RuntimeError(f"{cell.name} is listed for "
                                       f"{m['name']}, which its job "
                                       f"does not measure")
        job.free()
        checks = job.check()
    finally:
        compiles.close()
    ok = compare.passed(checks) and win["failed"] == 0
    stderr = [f"window: {win['attempted']} attempted, {win['failed']} "
              f"failed, {win['elapsed_s']:.6f} s, {compiles.n} compiles "
              f"inside; setup_s {setup_s:.6f}; {win.get('note', '')}",
              "setup: " + ", ".join(f"{n} {t:.3f} s" for n, t in phases)]
    stderr += [f"check {c['name']} {c['value']!r} limit {c['limit']!r} "
               f"(worst at {c['where']})" for c in checks]
    line = {"correct": bool(ok), "attempted": win["attempted"],
            "failed": win["failed"], "metrics": metrics, "device": info}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    return {"line": line, "stderr": stderr}
