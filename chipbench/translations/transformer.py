"""The dense decoder's published configuration keys as the program's
``ModelConfig`` fields.  ``KEYS`` are the keys taken; ``fields(c)``
gives the fields they set."""

# configuration-file key -> ModelConfig field
_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
           "tie_word_embeddings": "tie_embeddings", "qk_norm": "qk_norm",
           "attention_bias": "qkv_bias"}
_ACT = {"silu": "silu", "gelu_new": "gelu"}
_DTYPE = {"bfloat16": "bfloat16", "float32": "float32"}

KEYS = frozenset(_FIELDS) | {"hidden_act", "torch_dtype"}


def fields(c):
    kw = {field: c[key] for key, field in _FIELDS.items()}
    kw.update(mlp_act=_ACT[c["hidden_act"]], dtype=_DTYPE[c["torch_dtype"]],
              emb_dtype=_DTYPE[c["torch_dtype"]])
    return kw
