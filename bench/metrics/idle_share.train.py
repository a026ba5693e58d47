"""Share of the traced training window in which no operation ran on the
device: 1 - busy / window, from the profiler's trace."""


def read(ctx):
    if "train_tokens_per_s" not in ctx["window"]["end_to_end"]:
        return None
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
