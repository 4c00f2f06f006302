"""A DeepSeek-V3-style configuration's published keys as the program's
``ModelConfig`` fields.  ``KEYS`` are the keys taken; ``fields(c)``
gives the fields they set, and raises for a value the program does not
implement (q-LoRA, grouped routing, another selection method)."""
from repro.config import MoEConfig

# configuration-file key -> ModelConfig field
_FIELDS = {"hidden_size": "d_model", "moe_intermediate_size": "d_ff",
           "intermediate_size": "dense_d_ff",
           "first_k_dense_replace": "first_k_dense",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads",
           "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
           "max_position_embeddings": "max_seq_len",
           "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
           "tie_word_embeddings": "tie_embeddings",
           "attention_bias": "qkv_bias", "kv_lora_rank": "kv_lora_rank",
           "qk_nope_head_dim": "qk_nope_head_dim",
           "qk_rope_head_dim": "qk_rope_head_dim",
           "v_head_dim": "v_head_dim"}
# configuration-file key -> MoEConfig field
_MOE = {"n_routed_experts": "num_experts",
        "num_experts_per_tok": "num_experts_per_tok", "ep_size": "ep_size",
        "n_shared_experts": "n_shared_experts",
        "norm_topk_prob": "norm_topk_prob",
        "routed_scaling_factor": "routed_scaling_factor",
        "seq_aux": "seq_aux", "aux_loss_alpha": "router_aux_loss_coef"}
# keys the program takes at one value only
_FIXED = {"model_type": "deepseek_v3", "q_lora_rank": None, "n_group": 1,
          "topk_group": 1, "topk_method": "noaux_tc",
          "scoring_func": "sigmoid", "moe_layer_freq": 1,
          "num_nextn_predict_layers": 0, "hidden_act": "silu",
          "attention_bias": False, "tie_word_embeddings": False}
_DTYPE = {"bfloat16": "bfloat16", "float32": "float32"}

KEYS = frozenset(_FIELDS) | frozenset(_MOE) | frozenset(_FIXED) | {
    "torch_dtype"}


def fields(c):
    bad = {k: c[k] for k, v in _FIXED.items() if c[k] != v}
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        bad["num_key_value_heads"] = c["num_key_value_heads"]
    if bad:
        raise ValueError(f"the program does not implement {bad}")
    kw = {field: c[key] for key, field in _FIELDS.items()}
    kw.update(mlp_act="silu", dtype=_DTYPE[c["torch_dtype"]],
              emb_dtype=_DTYPE[c["torch_dtype"]],
              moe=MoEConfig(dispatch="grouped", selection_bias=True,
                            **{f: c[k] for k, f in _MOE.items()}))
    return kw
