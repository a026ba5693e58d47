"""Required work of the paged attention kernel ``_paged_kernel``: every
query reads the keys and values of its own context once, from the
blocks that hold it; nothing is read for pad positions or past the
context."""


def required(*, context_lens, n_heads: int, n_kv_heads: int,
             head_dim: int, queries: int = 1, kv_itemsize: int = 2,
             q_itemsize: int = 2):
    """(flops, bytes) of one call over requests with ``context_lens``
    cached positions each (``queries`` new positions per request)."""
    ctx = sum(int(c) for c in context_lens)
    n = len(context_lens)
    flops = 4 * ctx * queries * n_heads * head_dim      # q.k and p.v
    kv = 2 * ctx * n_kv_heads * head_dim * kv_itemsize
    q_out = 2 * n * queries * n_heads * head_dim * q_itemsize
    return flops, kv + q_out
