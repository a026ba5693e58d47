"""Required FLOPs of one training step by layer of the program for the
MLA model, as ``moe_lm_layers`` gives them for the multi-head one: the
terms of ``mla_moe_lm_train`` grouped by the layer that computes them,
so the groups sum to its ``step_flops``."""
from typing import Dict

from harness.spec import load_module

LAYER = load_module("flops", "moe_lm_layers").LAYER


def layer_flops(a, B: int, S: int) -> Dict[str, float]:
    """{layer: required FLOPs of one step over B rows of S tokens}."""
    train = load_module("flops", "mla_moe_lm_train")
    out: Dict[str, float] = {}
    for kind in ["dense"] * a["n_dense"] + ["moe"] * a["n_moe"]:
        for term, (fwd, trained) in train._layer(a, B, S, kind).items():
            layer = LAYER[term]
            out[layer] = out.get(layer, 0.0) + fwd * (
                3 if trained in (True, None) else 2)
    out["head"] = 3 * 2 * B * S * a["D"] * a["V"]
    return out
