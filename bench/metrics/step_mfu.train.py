"""Whole training step's share of the chip's bf16 peak: required FLOPs of
a step (``flops/<job's FLOPS module>``) times steps per second of the
traced window, over the peak of the chips used.  Host clock."""
from harness.spec import load_module


def read(ctx):
    w = ctx["window"]
    if "train_tokens_per_s" not in w["end_to_end"]:
        return None
    work = ctx["work"]
    flops = load_module("flops", ctx["flops"]).step_flops(
        work["arch"], work["batch"], work["seq"])
    rate = w["steps"] / w["elapsed_s"]
    return 100.0 * flops * rate / (ctx["peaks"]["bf16_flops"] * ctx["chips"])
