"""Which layer of the program each device operation belongs to, read
from the ``op_name`` metadata of the compiled programs' HLO.

The program opens a ``jax.named_scope`` at each layer boundary, and XLA
keeps the scopes open at trace time in every instruction's ``op_name``:
``jit(epoch)/while/body/closed_call/transpose(jvp(stack))/while/body/
closed_call/checkpoint/attention/dot_general``.  Transformations wrap a
scope's name (``jvp(...)``, ``transpose(...)``); unwrapped, the innermost
name in ``LAYERS`` is the instruction's layer.  A sub-scope of a layer
(``SUBSCOPES``) found inside it is reported as ``<layer>/<sub>``.  A
fusion goes by the scope of its heaviest operation (the order of
``trace._HEAVY``, as its label does); one with no heavy operation by its
own ``op_name``, else by the first operation inside with a known layer.
An instruction with no known layer is ``UNSCOPED``.

The benchmark does not import the program, so the names are kept here;
a test of the program ties them to its ``repro/utils/scopes.py``.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Tuple

from harness import trace

LAYERS = ("embed", "attention", "router", "experts", "shared_expert", "mlp",
          "norm", "stack", "head", "optimizer", "vaa", "kd_loss")
SUBSCOPES = {"experts": ("dispatch", "ffn", "combine")}
UNSCOPED = "unscoped"

_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = ([^\n]*)$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")


def scope_of(op_name: str) -> str:
    """The layer (or ``<layer>/<sub>``) of one ``op_name``."""
    layer = sub = None
    for part in op_name.split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        if part in LAYERS:
            layer, sub = part, None
        elif part in SUBSCOPES.get(layer, ()):
            sub = part
    if layer is None:
        return UNSCOPED
    return f"{layer}/{sub}" if sub else layer


def _parse(body: str) -> List[Tuple[str, str, str, str]]:
    """(name, opcode, scope, called computation or '') of each
    instruction of one computation's body."""
    out = []
    for m in _INSTR.finditer(body):
        rest = m.group(2)
        op = trace._OPCODE.search("= " + rest)
        name = _OP_NAME.search(rest)
        calls = _CALLS.search(rest)
        out.append((m.group(1), op.group(1) if op else "",
                    scope_of(name.group(1)) if name else UNSCOPED,
                    calls.group(1) if calls else ""))
    return out


def instruction_scopes(hlo_texts: Iterable[str]) -> Dict[str, str]:
    """HLO instruction name -> scope, for every instruction of the
    compiled programs' text (an operation in the trace is named by its
    instruction)."""
    out: Dict[str, str] = {}
    for text in hlo_texts:
        comps = {m.group(1): _parse(m.group(2))
                 for m in trace._COMP.finditer(text)}

        def fused(comp: str, seen=()) -> List[Tuple[str, str]]:
            """(opcode, scope) of every operation in a fused computation,
            nested fusions opened."""
            ops = []
            for _, op, scope, calls in comps.get(comp, ()):
                if op == "fusion" and calls and calls not in seen:
                    ops += fused(calls, seen + (calls,))
                else:
                    ops.append((op, scope))
            return ops

        for instrs in comps.values():
            for name, op, scope, calls in instrs:
                if op == "fusion" and calls:
                    scope = _fusion_scope(fused(calls, (calls,)), scope)
                out[name] = scope
    return out


def _fusion_scope(ops: List[Tuple[str, str]], own: str) -> str:
    opcodes = {op for op, _ in ops}
    heavy = next((h for h in trace._HEAVY if h in opcodes), None)
    if heavy is not None:
        for op, scope in ops:
            if op == heavy and scope != UNSCOPED:
                return scope
    if own != UNSCOPED:
        return own
    return next((s for _, s in ops if s != UNSCOPED), UNSCOPED)


def _known(label: str) -> bool:
    layer, _, sub = label.partition("/")
    return layer in LAYERS and (not sub or sub in SUBSCOPES.get(layer, ()))


def seconds(pd, window_span: str, devices: int,
            hlo_texts: Iterable[str]) -> Dict[str, float]:
    """Device self seconds per scope in the span ``window_span``, averaged
    over the chips: the trace reduction's per-operation self times, with
    each operation labelled by its scope.  They sum to the reduction's
    ``kernels``."""
    red = trace.reduce_profile(pd, window_span, devices,
                               instruction_scopes(hlo_texts))
    out: Dict[str, float] = {}
    for label, s in red["kernels"].items():
        label = label if _known(label) else UNSCOPED
        out[label] = out.get(label, 0.0) + s
    return out


def by_layer(scope_seconds: Dict[str, float]) -> Dict[str, float]:
    """Seconds per layer, sub-scopes counted toward their layer."""
    out: Dict[str, float] = {}
    for scope, s in scope_seconds.items():
        layer = scope.split("/", 1)[0]
        out[layer] = out.get(layer, 0.0) + s
    return out
