"""A DeepSeek-V3-architecture model as the system under test runs it:
the registry's ``deepseek-v3-671b`` family (MLA, leading dense layers,
sigmoid router with correction bias) with every size, the router's
settings, the RoPE base and the norm epsilon taken from the
configuration file, and no multi-token-prediction head where the file
has none.  Attention recomputes each chunk's scores in the backward
(``remat_attn_chunks``): at 8192 tokens the chunked attention's saved
float32 scores alone take 8 GiB, and the step does not fit one v5e
without it."""
from repro.configs import get_config


def program_config(cfg):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return get_config("deepseek-v3-671b").replace(
        n_layers=cfg["num_hidden_layers"], d_model=D, n_heads=H,
        n_kv_heads=cfg["num_key_value_heads"], head_dim=nope + rope,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        q_lora_rank=cfg["q_lora_rank"] or 0,
        kv_lora_rank=cfg["kv_lora_rank"], rope_head_dim=rope,
        nope_head_dim=nope, v_head_dim=cfg["v_head_dim"],
        n_experts=cfg["n_routed_experts"],
        n_shared_experts=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        n_mtp=cfg["num_nextn_predict_layers"],
        router_score=cfg["scoring_func"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        router_aux_coef=float(cfg["aux_loss_alpha"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        remat_attn_chunks=True,
        dtype=cfg["torch_dtype"]).validate()
