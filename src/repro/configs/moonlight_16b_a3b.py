"""Moonlight-16B-A3B: the DeepSeek-V3 block at 16B total, 3B active.
[hf:moonshotai/Moonlight-16B-A3B, config.json, model_type deepseek_v3]

Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1) with no
query LoRA: the cache holds one 576-wide latent a token (the normalized
512-wide c_kv and the 64-wide RoPE key shared by all heads).  Layer 0 has a
dense SwiGLU FFN; layers 1-26 route each token to 6 of 64 experts by sigmoid
score plus a correction bias (DeepSeek-V3, arXiv:2412.19437 §2.1.2;
``noaux_tc`` with one group is plain top-k), weight the chosen scores
normalized to sum 1 by 2.446, and add 2 shared experts.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe", n_layers=27, d_model=2048,
    n_heads=16, n_kv=16, d_ff=11264, vocab=163840, rope_theta=50000.0,
    norm_eps=1e-5, tie_embeddings=False,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
    first_k_dense=1, moe_score="sigmoid", moe_route_scale=2.446)
