"""The program's layers by name, as ``jax.named_scope`` writes them into
the ``op_name`` metadata of every HLO instruction traced inside.

A device profile names each operation by its HLO instruction, and the
instruction's ``op_name`` holds the scopes open when it was traced
(``jit(epoch)/transpose(jvp(stack))/while/body/attention/dot_general``),
so an operation belongs to the innermost layer named here.  Scopes are
trace-time metadata only: they change no computation.

The benchmark (``bench/harness/scopes.py``) keeps its own copy of these
names, since it does not import the program; a test ties the two.
"""
from __future__ import annotations

import functools

import jax

EMBED = "embed"
ATTENTION = "attention"
ROUTER = "router"
EXPERTS = "experts"            # the routed experts, on every MoE path
DISPATCH = "dispatch"          # stages of the expert-parallel paths,
FFN = "ffn"                    # inside EXPERTS
COMBINE = "combine"
SHARED_EXPERT = "shared_expert"
MLP = "mlp"                    # the FFN of a dense block
NORM = "norm"
STACK = "stack"                # a scan over layers: its own slicing and
                               # stacking, and what no inner scope holds
HEAD = "head"                  # LM head and its loss
OPTIMIZER = "optimizer"
VAA = "vaa"
KD_LOSS = "kd_loss"            # Phase II CE + KL over both heads

LAYERS = (EMBED, ATTENTION, ROUTER, EXPERTS, SHARED_EXPERT, MLP, NORM,
          STACK, HEAD, OPTIMIZER, VAA, KD_LOSS)
SUBSCOPES = {EXPERTS: (DISPATCH, FFN, COMBINE)}


def scoped(name: str):
    """Decorator: trace the function inside ``jax.named_scope(name)``.
    Each call opens a scope of its own: one ``named_scope`` object is not
    re-entrant, and a decorated function may run inside itself (a layer
    scan inside a layer scan)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
