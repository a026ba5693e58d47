"""A DeepSeek-V3-architecture model (``model_type`` deepseek_v3, e.g.
Moonlight-16B-A3B) read into the plain MLA reference's terms: MLA with
a full-rank query, ``first_k_dense_replace`` leading dense layers, then
expert layers under the sigmoid router (``noaux_tc``) with the
sequence-wise balance term."""


def arch(cfg):
    checks = {"q_lora_rank": None, "scoring_func": "sigmoid",
              "topk_method": "noaux_tc", "seq_aux": True,
              "norm_topk_prob": True,
              "moe_layer_freq": 1, "num_nextn_predict_layers": 0,
              "hidden_act": "silu", "attention_bias": False}
    for key, want in checks.items():
        if cfg[key] != want:
            raise ValueError(f"only {key}={want!r} is described here, "
                             f"not {cfg[key]!r}")
    if cfg.get("rope_scaling"):
        raise ValueError("rope_scaling is not described here")
    n_dense = cfg["first_k_dense_replace"]
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "V": cfg["vocab_size"], "r": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "n_dense": n_dense,
            "n_moe": cfg["num_hidden_layers"] - n_dense,
            "F_dense": cfg["intermediate_size"],
            "E": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "F": cfg["moe_intermediate_size"],
            "F_shared": cfg["n_shared_experts"]
            * cfg["moe_intermediate_size"],
            "n_group": cfg["n_group"], "topk_group": cfg["topk_group"],
            "scale": float(cfg["routed_scaling_factor"]),
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "aux_coef": float(cfg["aux_loss_alpha"]),
            "dtype": cfg["torch_dtype"]}
