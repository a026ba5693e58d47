"""How unevenly the router spreads a training batch over the experts:
the busiest expert's assignments over the mean, T * top_k / E, the
largest over the expert layers.  The job counts them for the window's
last batch under the window's final weights when this reader calls its
window's ``expert_load``, after the window: one row of per-expert counts
per expert layer.  The busiest expert sets the largest group of every
grouped matmul of its layer."""


def read(ctx):
    w = ctx["window"]
    if "train_tokens_per_s" not in w["end_to_end"] or "expert_load" not in w:
        return None
    return max(max(row) * len(row) / sum(row) for row in w["expert_load"]())
