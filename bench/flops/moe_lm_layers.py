"""Required FLOPs of one training step by layer of the program (the
layer names of ``harness/scopes.py``), the numerators of per-layer MFU.

The terms and the multipliers are ``moe_lm_train``'s, grouped: forward
and backward (x3) of what is trained and of the weight-free attention
core, forward and input gradient (x2) of frozen weights; routed rows
are tokens x top_k; recomputation does not count.  So the groups sum to
``moe_lm_train.step_flops``.
"""
from typing import Dict

from harness.spec import load_module

# moe_lm_train's term -> the program's layer that computes it
LAYER = {"attn_proj": "attention", "attn_core": "attention",
         "router": "router", "experts": "experts",
         "shared": "shared_expert", "mlp": "mlp"}


def layer_flops(a, B: int, S: int) -> Dict[str, float]:
    """{layer: required FLOPs of one step over B rows of S tokens}."""
    train = load_module("flops", "moe_lm_train")
    out: Dict[str, float] = {}
    for kind in ["dense"] * a["n_dense"] + ["moe"] * a["n_moe"]:
        for term, (fwd, trained) in train._layer(a, B, S, kind).items():
            layer = LAYER[term]
            out[layer] = out.get(layer, 0.0) + fwd * (
                3 if trained in (True, None) else 2)
    out["head"] = 3 * 2 * B * S * a["D"] * a["V"]
    return out
