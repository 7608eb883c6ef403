"""Moonlight-16B-A3B — DeepSeek-V3 block: latent attention, 64 routed
experts top-6 with 2 shared, one leading dense layer.

[hf:moonshotai/Moonlight-16B-A3B config.json, ``model_type`` deepseek_v3].
27 layers, d_model 2048, 16 heads. MLA without a q LoRA: q/k heads of
qk_nope 128 + qk_rope 64, a 512-wide KV latent, v heads of 128, RoPE theta
50000 on the rope part only. Layer 0 is a dense SwiGLU of 11264; layers
1-26 route each token by sigmoid scores (``noaux_tc``, one group) to 6 of
64 SwiGLU experts of 1408, normalise the chosen scores and scale them by
2.446, and add 2 shared experts. Untied vocabulary of 163840, RMSNorm eps
1e-5.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    num_experts=64,
    top_k=6,
    moe_d_ff=1408,
    moe_scoring="sigmoid",
    routed_scaling=2.446,
    shared_experts=2,
    first_dense_layers=1,
    norm_eps=1e-5,
)
