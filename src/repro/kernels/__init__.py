"""Pallas TPU kernels for the pipeline's compute hot spots.

Each kernel ships three files:
  kernel.py - ``pl.pallas_call`` body with explicit BlockSpec VMEM tiling,
  ops.py    - jit-able public wrapper (interpret=True on CPU),
  ref.py    - pure-jnp oracle the tests sweep against.

Kernels:
  moe_gemm        - grouped expert FFN (E, cap, D) x (E, D, F) for the
                    expert-parallel MoE layers inside ``shard_map``;
                    custom-VJP backward as grouped GEMMs (trainable).
  moe_dispatch    - fused token permute/unpermute (gather/scatter-add)
                    shared by the expert-parallel MoE paths; custom VJP.
  ssd_scan        - Mamba-2 SSD chunked scan (intra-chunk quadratic +
                    carried state).
  kd_loss         - fused CE + KL over large vocabularies straight from
                    hidden states (the KD server hot spot; never
                    materialises (T, V) logits in HBM).
  paged_attn      - block-paged decode attention: the per-slot block
                    table is scalar-prefetched so each grid cell DMAs
                    exactly the KV pool rows its slot owns (serving).
"""
