"""moonlight-16b-a3b [moe]: 27L d_model=2048 16H MLA (kv_lora 512, q/k
heads 128 + 64 rotary, v heads 128), layer 0 dense (MLP 11264), then 26
layers of 64 routed experts of width 1408 (top 6, sigmoid scores with a
selection bias, normalized and scaled by 2.446) and 2 shared experts;
vocab=163840, context 8192.  Trained with Muon (arXiv 2502.16982).
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]

The expert layers run the dropless grouped path; ``ep_size`` says over
how many chips each layer's routed experts are split (this layer holds
rank 0's share).  Latent attention has no decode cache yet, so the
architecture trains and prefills only.
"""
from repro.config import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=1408, vocab_size=163840, max_seq_len=8192,
    rope_theta=50000.0, norm_eps=1e-5, mlp_act="silu",
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, first_k_dense=1, dense_d_ff=11264,
    moe=MoEConfig(num_experts=64, num_experts_per_tok=6, dispatch="grouped",
                  ep_size=1, n_shared_experts=2, selection_bias=True,
                  norm_topk_prob=True, routed_scaling_factor=2.446,
                  seq_aux=True, router_aux_loss_coef=1e-4),
)

SMOKE = CONFIG.replace(
    name="moonlight-smoke", num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=4, d_ff=32, vocab_size=256, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, dense_d_ff=96,
    moe=MoEConfig(num_experts=16, num_experts_per_tok=4, dispatch="grouped",
                  ep_size=2, n_shared_experts=2, selection_bias=True,
                  norm_topk_prob=True, routed_scaling_factor=2.446,
                  seq_aux=True, router_aux_loss_coef=1e-4))
