"""Required FLOPs of one training step of the reference's decoder (a
plain or expert language model), the numerator of ``step_mfu``.

Forward and backward of everything trained; forward and input gradient
of what is frozen (the expert FFNs in Phase III) and nothing of a
frozen teacher but its forward.  Routed rows are tokens x top_k.
Causal attention counts half of its square.  Recomputation does not
count.  Norms, softmaxes and the optimizer are not counted: they are
memory-bound and a small share of the work.
"""

from reference.moe_lm import frozen


def _layer(a, B, S, kind):
    """{name: (fwd flops, trained)} of one layer."""
    T = B * S
    D, H, KH, Dh = a["D"], a["H"], a["KH"], a["Dh"]
    out = {"attn_proj": (2 * T * D * (2 * H * Dh + 2 * KH * Dh), True),
           "attn_core": (2 * B * S * S * H * Dh, None)}
    if kind == "dense":
        out["mlp"] = (6 * T * D * a["F_dense"], not frozen("mlp/wo"))
    else:
        out["router"] = (2 * T * D * a["E"], True)
        out["experts"] = (6 * T * a["k"] * D * a["F"],
                          not frozen("moe/wo"))
        if a["F_shared"]:
            out["shared"] = (6 * T * D * a["F_shared"],
                             not frozen("moe/shared/wo"))
    return out


def step_flops(a, B: int, S: int) -> float:
    """Required FLOPs of one step over B rows of S tokens.  The
    attention core has no weights: its backward is twice its forward."""
    total = 0.0
    layers = ["dense"] * a["n_dense"] + ["moe"] * a["n_moe"]
    for kind in layers:
        for fwd, trained in _layer(a, B, S, kind).values():
            total += fwd * (3 if trained in (True, None) else 2)
    total += 3 * 2 * B * S * a["D"] * a["V"]     # the head
    return total
