"""Qwen1.5-MoE (``model_type`` qwen2_moe) read into the plain reference's
terms: one shared expert of ``shared_expert_intermediate_size``; every
layer has experts (``decoder_sparse_step`` 1)."""


def arch(cfg):
    if cfg["decoder_sparse_step"] != 1:
        raise ValueError("only decoder_sparse_step 1 is described here")
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"D": D, "H": H, "KH": cfg["num_key_value_heads"], "Dh": D // H,
            "V": cfg["vocab_size"], "n_dense": 0,
            "n_moe": cfg["num_hidden_layers"], "F_dense": 0,
            "E": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
            "F": cfg["moe_intermediate_size"],
            "F_shared": cfg["shared_expert_intermediate_size"],
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "aux_coef": float(cfg["router_aux_loss_coef"]),
            "dtype": cfg["torch_dtype"]}
