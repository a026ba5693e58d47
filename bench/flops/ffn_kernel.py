"""Required work of the grouped expert FFN kernels (``_ffn_kernel``
forward, ``_matmul_kernel`` backward) over one training step.

Only routed rows count: tokens x top_k, never the padded capacity.  The
experts are frozen in Phase III, so they need the forward and the input
gradient and no weight gradient.  Recomputation does not count.
"""


def required(*, tokens: int, top_k: int, d_model: int, d_expert: int,
             n_experts: int, n_layers: int, itemsize: int = 2,
             train: bool = True, frozen: bool = True):
    """(flops, bytes) of ``n_layers`` expert layers over ``tokens``."""
    rows = tokens * top_k
    fwd = 6 * rows * d_model * d_expert          # gate, up, down
    passes = 1 + (1 if train else 0) + (1 if train and not frozen else 0)
    flops = passes * fwd
    weights = 3 * n_experts * d_model * d_expert * itemsize
    acts = 2 * rows * d_model * itemsize         # rows in, rows out
    nbytes = passes * weights + (2 if train else 1) * acts
    return n_layers * flops, n_layers * nbytes
