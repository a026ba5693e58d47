"""Required FLOPs of one training step of the MLA reference's decoder
(``reference/mla_moe_lm.py``), the numerator of ``step_mfu``.

The rules of ``moe_lm_train``: forward and backward (x3) of everything
trained and of the weight-free attention core; forward and input
gradient (x2) of what is frozen (the expert FFNs in Phase III).  Routed
rows are tokens x top_k.  Causal attention counts half of its square.
Recomputation does not count.  Norms, softmaxes, the router's choice and
the optimizer are not counted: they are memory-bound and a small share
of the work.

MLA's work is its five projections, W_q (D -> H (nope + rope)), W_kva
(D -> r + rope), W_kb and W_vb (r -> H nope, r -> H v, per token) and
W_o (H v -> D), and its core: scores over nope + rope and the weighted
sum over v, 2 S^2 H (nope + rope + v) FLOPs a sequence over the whole
square.
"""

from reference.mla_moe_lm import frozen


def _layer(a, B, S, kind):
    """{name: (fwd flops, trained)} of one layer."""
    T = B * S
    D, H, r = a["D"], a["H"], a["r"]
    nope, rope, v = a["nope"], a["rope"], a["v"]
    proj = D * H * (nope + rope) + D * (r + rope) + r * H * (nope + v) \
        + H * v * D
    out = {"attn_proj": (2 * T * proj, True),
           "attn_core": (B * S * S * H * (nope + rope + v), None)}
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
