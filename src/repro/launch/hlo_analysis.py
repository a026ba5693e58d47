"""Roofline terms from a lowered/compiled XLA module.

``cost_analysis()`` reports FLOPs and HBM bytes but NOT collective
traffic — we parse the (post-SPMD, per-device) HLO text and sum the
output bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute instruction.

The module also carries the EP-A2A overlap check
(``a2a_overlap_pairs`` / ``assert_a2a_overlap``): a def-use analysis
over the compiled HLO that proves an ``all-to-all`` has matmul work it
is dataflow-independent of — the structural precondition for XLA's
latency-hiding scheduler to actually run the collective concurrently
with compute (what ``cfg.overlap_a2a``'s half-batch split buys).

``tpu_kernels`` names the Pallas kernels a lowered module hands the TPU
compiler.
"""
from __future__ import annotations

import math
import re
from collections import Counter, defaultdict
from typing import Dict, List, Set, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  bf16[16,2048,128]{2,1,0}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


_KERNEL_NAME_RE = re.compile(r'kernel_name = "([^"]+)"')


def tpu_kernels(stablehlo_text: str) -> Counter:
    """Pallas TPU kernels in a lowered (StableHLO) module: kernel name ->
    number of ``tpu_custom_call`` ops that run it."""
    out: Counter = Counter()
    for line in stablehlo_text.splitlines():
        if "@tpu_custom_call(" in line:
            m = _KERNEL_NAME_RE.search(line)
            out[m.group(1) if m else "?"] += 1
    return out


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output bytes per collective kind (per device, per step)."""
    out = {k: 0 for k in _COLLECTIVES}
    out["total"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if not s or "=" not in s:
            continue
        # "%x = TYPE[...] op-name(...)" or tuple "( ... )"
        rhs = s.split("=", 1)[1]
        opm = re.search(r"\)?\s*([a-z0-9-]+)\(", rhs)
        if not opm:
            continue
        op = opm.group(1)
        kind = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-"):  # e.g. all-gather-start
                kind = c
                break
        if kind is None:
            continue
        if op.endswith("-done"):
            continue  # avoid double counting start/done pairs
        type_part = rhs[:opm.start()]
        nbytes = sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(type_part))
        out[kind] += nbytes
        out["total"] += nbytes
    return out


# ---------------------------------------------------------------------------
# EP-A2A overlap: def-use independence of collectives vs matmul work
# ---------------------------------------------------------------------------

_OP_RE = re.compile(r"\)?\s*([a-z0-9-]+)\(")
_INSTR_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(.*)$")
_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s*[({]")
_NAME_RE = re.compile(r"%?([\w.-]+)")


def _parse_computations(hlo_text: str):
    """HLO text -> {computation: [(name, op, operand_names, raw_rhs)]}.

    Tolerant line-based parse of both ``%name = ...`` and bare-name HLO
    dialects; operand extraction is conservative (any identifier in the
    rhs that is defined in the same computation counts as a dependency,
    so control/attribute references only ever ADD edges — the
    independence verdict can under-report, never over-report).
    """
    comps: Dict[str, List[Tuple[str, str, List[str], str]]] = {}
    cur = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if not s:
            continue
        if s.endswith("{") and "=" not in s.split("(", 1)[0]:
            m = _HDR_RE.match(s)
            if m:
                cur = m.group(1)
                comps[cur] = []
            continue
        if s == "}" or s.startswith("}"):
            continue
        if cur is None:
            continue
        im = _INSTR_RE.match(s)
        if not im:
            continue
        name, rhs = im.group(1), im.group(2)
        om = _OP_RE.search(rhs)
        if not om:
            continue
        comps[cur].append((name, om.group(1), [], rhs))
    # second pass: operands = identifiers defined in the same computation
    for cname, instrs in comps.items():
        defined = {n for n, _, _, _ in instrs}
        for entry in instrs:
            name, _, operands, rhs = entry
            for nm in _NAME_RE.findall(rhs):
                if nm in defined and nm != name:
                    operands.append(nm)
    return comps


def _dot_bearing(comps, cname: str) -> Set[str]:
    """Names of instructions in ``cname`` that carry matmul work: a
    ``dot``/``convolution``, a matmul custom-call, or a fusion/call whose
    called computation (transitively) contains one."""
    memo: Dict[str, bool] = {}

    def comp_has_dot(c: str) -> bool:
        if c not in comps:
            return False
        if c not in memo:
            memo[c] = False  # cycle guard
            memo[c] = any(_is_dot(op, rhs) for _, op, _, rhs in comps[c])
        return memo[c]

    def _is_dot(op: str, rhs: str) -> bool:
        if op in ("dot", "convolution"):
            return True
        if op == "custom-call" and ("gemm" in rhs or "matmul" in rhs
                                    or "dot" in rhs):
            return True
        if op in ("fusion", "call", "async-start"):
            m = re.search(r"(?:calls|to_apply)=%?([\w.-]+)", rhs)
            return bool(m) and comp_has_dot(m.group(1))
        return False

    return {name for name, op, _, rhs in comps.get(cname, ())
            if _is_dot(op, rhs)}


def _closure(start: str, edges) -> Set[str]:
    out, todo = set(), [start]
    while todo:
        n = todo.pop()
        for nxt in edges(n):
            if nxt not in out:
                out.add(nxt)
                todo.append(nxt)
    return out


def a2a_overlap_pairs(hlo_text: str):
    """Per ``all-to-all``: how much matmul work it could overlap with.

    Returns [(computation, a2a_name, n_independent_dots)] — a
    dot-bearing instruction is *independent* of the collective when it
    is neither an ancestor nor a descendant in the computation's def-use
    graph, i.e. nothing forces it to run before or after, so the
    scheduler is free to run them concurrently.  ``-done`` halves of
    async pairs are skipped (their ``-start`` carries the dependencies).
    """
    comps = _parse_computations(hlo_text)
    results = []
    for cname, instrs in comps.items():
        ops = {name: operands for name, _, operands, _ in instrs}
        users = defaultdict(set)
        for name, _, operands, _ in instrs:
            for o in operands:
                users[o].add(name)
        dots = _dot_bearing(comps, cname)
        for name, op, _, _ in instrs:
            if not op.startswith("all-to-all") or op.endswith("-done"):
                continue
            anc = _closure(name, lambda n: ops.get(n, ()))
            desc = _closure(name, lambda n: users[n])
            results.append((cname, name, len(dots - anc - desc)))
    return results


def assert_a2a_overlap(hlo_text: str) -> None:
    """Raise unless some ``all-to-all`` has dataflow-independent matmul
    work available to overlap with (the ``cfg.overlap_a2a`` guarantee)."""
    pairs = a2a_overlap_pairs(hlo_text)
    if not pairs:
        raise AssertionError("no all-to-all instruction in the module — "
                             "is the MoE a2a path actually sharded?")
    if not any(n > 0 for _, _, n in pairs):
        raise AssertionError(
            "no all-to-all has dataflow-independent matmul work; the "
            "collective cannot overlap compute: "
            + ", ".join(f"{c}/{a}" for c, a, _ in pairs[:8]))


def roofline_terms(cost: Dict, coll: Dict[str, int], *, peak_flops: float,
                   hbm_bw: float, ici_bw: float) -> Dict[str, float]:
    """All inputs are per-device.  Terms in seconds."""
    # clamp: two-point calibration slopes can go microscopically negative
    flops = max(float(cost.get("flops", 0.0)), 0.0)
    bytes_hbm = max(float(cost.get("bytes accessed", 0.0)), 0.0)
    bytes_coll = max(float(coll.get("total", 0)), 0.0)
    t_compute = flops / peak_flops
    t_memory = bytes_hbm / hbm_bw
    t_coll = bytes_coll / ici_bw
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    return {
        "flops_per_device": flops,
        "hbm_bytes_per_device": bytes_hbm,
        "collective_bytes_per_device": bytes_coll,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
    }
