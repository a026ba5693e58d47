"""Qwen1.5-MoE as the system under test runs it: the registry's
``qwen2-moe-a2.7b`` family, with every size, the RoPE base and the
load-balance weight taken from the configuration file, on the Pallas
path."""
from repro.configs import get_config


def program_config(cfg):
    shared = cfg["shared_expert_intermediate_size"]
    F = cfg["moe_intermediate_size"]
    if shared % F:
        raise ValueError("the shared lane must be whole experts wide")
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return get_config("qwen2-moe-a2.7b").replace(
        n_layers=cfg["num_hidden_layers"], d_model=D, n_heads=H,
        n_kv_heads=cfg["num_key_value_heads"], head_dim=D // H,
        d_ff=shared, vocab_size=cfg["vocab_size"],
        n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        moe_d_ff=F, n_shared_experts=shared // F,
        rope_theta=float(cfg["rope_theta"]),
        router_aux_coef=float(cfg["router_aux_loss_coef"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"]).validate()
