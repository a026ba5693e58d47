"""From the profiler's trace of the window to busy time, kernel time and
the breakdown.

The trace (``jax.profiler``'s ``.xplane.pb``) holds, on the same clock:

* per chip a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` has one
  event per operation that ran on the device;
* host planes whose events include the benchmark's spans
  (``bench.<name>``, see ``spans.py``).

Busy time is the union of a chip's operation intervals inside the
window span, averaged over the chips.  An operation's event is named by
its HLO instruction (``%grouped_ffn.1 = ... custom-call(...)``); events
nest (a ``while`` holds its body's operations), so each operation is
given its self time, its own interval less its children's.  A Pallas
kernel is a ``tpu_custom_call`` whose serialized body holds the kernel's
own name (``_ffn_kernel``); ``op_labels`` reads it from the compiled
programs' HLO, so a kernel is found by that name however the instruction
is called.  A fusion goes by the heaviest operation inside it
(``fusion:convolution``), anything else by its instruction's name
without the number.  An idle gap is a stretch of the window with no
operation on the device, labelled with the innermost benchmark span open
on the host at its middle.
"""
from __future__ import annotations

import base64
import contextlib
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

import jax

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10

Interval = Tuple[int, int]


@contextlib.contextmanager
def recording(tdir: str):
    jax.profiler.start_trace(tdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(tdir: str) -> str:
    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return max(files, key=os.path.getmtime)


_CUSTOM = re.compile(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"'
                     r'[^\n]*?"body":"([A-Za-z0-9+/=]*)"')
_KERNEL = re.compile(rb"[A-Za-z_][A-Za-z0-9_]*kernel[A-Za-z0-9_]*")


_HEAVY = ("convolution", "dot", "scatter", "gather", "sort",
          "reduce-window", "reduce", "dynamic-update-slice",
          "dynamic-slice", "all-to-all", "all-reduce", "all-gather")
_COMP = re.compile(r"^%?(\S+) [^\n]*\{\n(.*?)^\}", re.M | re.S)
_OPCODE = re.compile(r"= .*?\b([a-z][a-z0-9-]*)\(")
_FUSION = re.compile(r"%(\S+) = .*? fusion\(.*?calls=%([\w.\-]+)")


def kernel_names(hlo_texts: Iterable[str]) -> Dict[str, str]:
    """HLO instruction name -> Pallas kernel name, for every
    ``tpu_custom_call`` in the compiled programs' text."""
    out = {}
    for text in hlo_texts:
        for m in _CUSTOM.finditer(text):
            names = _KERNEL.findall(base64.b64decode(m.group(2)))
            if names:
                out[m.group(1)] = names[0].decode()
    return out


def op_labels(hlo_texts: Iterable[str]) -> Dict[str, str]:
    """HLO instruction name -> label: the kernel's name for a Pallas
    kernel, ``fusion:<heaviest op inside>`` for a fusion."""
    texts = list(hlo_texts)
    out = {}
    for text in texts:
        comps = {m.group(1): m.group(2) for m in _COMP.finditer(text)}
        for m in _FUSION.finditer(text):
            ops = set(_OPCODE.findall(comps.get(m.group(2), "")))
            heavy = next((h for h in _HEAVY if h in ops), None)
            out[m.group(1)] = f"fusion:{heavy}" if heavy else "fusion"
    out.update(kernel_names(texts))
    return out


def op_name(event_name: str, kernels: Dict[str, str]) -> str:
    """What a reader knows an operation by: its kernel's name, or its
    instruction's name without the number (``fusion.12`` -> ``fusion``)."""
    m = re.match(r"%?([^\s=]+)", event_name)
    instr = m.group(1) if m else event_name
    if instr in kernels:
        return kernels[instr]
    return re.sub(r"[.\d]+$", "", instr) or instr


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def self_times(evs: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """Per label, the time of its events less that of the events nested
    directly inside them."""
    out: Dict[str, int] = {}
    stack: List[Tuple[int, str]] = []      # (end, label) of open events
    for s, e, k in sorted(evs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out[k] = out.get(k, 0) + (e - s)
        if stack:
            out[stack[-1][1]] -= min(e, stack[-1][0]) - s
        stack.append((e, k))
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def reduce_profile(pd, window_span: str, devices: int = 1,
                   kernels_by_instr: Dict[str, str] = None) -> Dict:
    """Busy and window seconds, per-kernel device seconds, the top device
    operations and the longest idle gaps of the span ``window_span``."""
    spans: List[Tuple[str, int, int]] = []
    dev_planes = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev_planes[int(m.group(1))] = plane
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    wins = [(s, e) for n, s, e in spans if n == window_span]
    if len(wins) != 1:
        raise ValueError(f"{len(wins)} spans named {window_span!r} in the "
                         f"trace, expected 1")
    w0, w1 = wins[0]
    used = sorted(dev_planes)[:devices]
    if len(used) < devices:
        raise ValueError(f"{len(used)} device planes in the trace, "
                         f"expected {devices}")
    busy_ns, kernels = 0, {}
    gaps: List[Tuple[int, int]] = []
    for idx in used:
        evs = []
        for line in dev_planes[idx].lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                             w0, w1)
                if e > s:
                    evs.append((s, e, op_name(ev.name, kernels_by_instr or {})))
        for k, t in self_times(evs).items():
            kernels[k] = kernels.get(k, 0) + t
        busy = union([(s, e) for s, e, _ in evs])
        busy_ns += sum(e - s for s, e in busy)
        if idx == used[0]:
            edges = [w0] + [x for iv in busy for x in iv] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    n = len(used)
    inner = [(nm, s, e) for nm, s, e in spans if nm != window_span]

    def label(g0, g1):
        mid = (g0 + g1) / 2
        open_ = [(s, nm) for nm, s, e in inner if s <= mid < e]
        return max(open_)[1] if open_ else "no benchmark span"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "kernels": {k: v / n / 1e9 for k, v in kernels.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(kernels.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label(g0, g1), (g1 - g0) / 1e9]
                      for g0, g1 in gaps[:TOP]],
    }


def reduce(tdir: str, window_span: str, devices: int = 1,
           hlo_texts: Iterable[str] = ()) -> Dict:
    pd = jax.profiler.ProfileData.from_file(xplane_file(tdir))
    return reduce_profile(pd, window_span, devices, op_labels(hlo_texts))
