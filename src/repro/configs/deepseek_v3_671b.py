"""DeepSeek-V3 671B — MLA + 1 shared / 256 routed top-8 MoE + MTP.
[arXiv:2412.19437]

61 layers (first 3 dense FFN @ 18432), d_model=7168; multi-head latent
attention (kv_lora=512, rope=64, nope=128, v=128, q_lora=1536); 256
routed experts (d_ff 2048, top-8) + 1 shared expert; one MTP head.
Its router (§2.1.2, ``topk_method`` noaux_tc): sigmoid scores, top-8 of
score plus a correction bias among the 4 best of 8 expert groups,
weights renormalised and scaled by 2.5, and the sequence-wise balance
term at alpha 1e-4 (§4.2).
The MLA latent cache (576 f/token/layer) is what lets this config run
``long_500k`` (DESIGN.md §6).
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    arch_type="moe",
    citation="arXiv:2412.19437",
    n_layers=61,
    d_model=7168,
    n_heads=128, n_kv_heads=128, head_dim=128,
    d_ff=18432,             # dense FFN width of the 3 leading layers
    vocab_size=129280,
    attn_type="mla",
    q_lora_rank=1536, kv_lora_rank=512,
    rope_head_dim=64, nope_head_dim=128, v_head_dim=128,
    n_experts=256, n_shared_experts=1, top_k=8, moe_d_ff=2048,
    first_dense_layers=3,
    router_score="sigmoid", n_group=8, topk_group=4,
    routed_scaling_factor=2.5, router_aux_coef=1e-4,
    n_mtp=1,
    tie_embeddings=False,
).validate()
