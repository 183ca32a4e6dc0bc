"""moonlight-16b-a3b: DeepSeek-V3 block at 2,048 wide, 27 layers
[hf:moonshotai/Moonlight-16B-A3B, config.json, model_type deepseek_v3].

Multi-head latent attention (16 heads, kv_lora_rank 512, q_lora_rank null,
qk_nope/qk_rope/v head dims 128/64/128), one leading dense SwiGLU layer of
width 11,264, then 26 MoE layers of 64 routed experts (width 1,408, 6 a
token, sigmoid scores, ``noaux_tc`` top-k with a correction bias, n_group =
topk_group = 1, normalised, x2.446) and 2 shared experts. rope_theta 50,000,
RMSNorm eps 1e-5, vocabulary 163,840 with an untied head, context 8,192.

``experts_held`` 0 holds all 64 experts; a serving deployment sets it to the
chip's share (``bench/configs/moonlight_ep8.json``: 8 of 64).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_head=192,  # qk_nope_head_dim + qk_rope_head_dim
    d_ff=11264,
    dense_d_ff=11264,
    vocab_size=163840,
    activation="swiglu",
    norm_eps=1e-5,
    rope_theta=50_000.0,
    num_experts=64,
    num_shared_experts=2,
    moe_top_k=6,
    moe_d_ff=1408,
    scoring_func="sigmoid",
    topk_method="noaux_tc",
    norm_topk_prob=True,
    routed_scaling_factor=2.446,
    first_k_dense_replace=1,
    kv_lora_rank=512,
    q_lora_rank=0,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
)
