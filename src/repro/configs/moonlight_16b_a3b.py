"""moonlight-16b-a3b [moe] — Moonshot AI's Moonlight-16B-A3B
(https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json,
``model_type`` deepseek_v3).

27L, d_model=2048, 16 heads of latent attention (kv_lora_rank 512, no q
latent; q/k heads 128 + 64 RoPE dims, v heads 128), RoPE theta 5e4. One
leading dense layer (SwiGLU d_ff 11264), then 26 MoE layers: 64 routed
experts of width 1408, 6 per token, sigmoid scores with a selection bias
(noaux_tc, one group), gates normed and scaled by 2.446, and 2 shared
experts (one MLP of width 2816). Vocab 163840, untied head, RMSNorm eps
1e-5. About 15.96 B parameters, 2.91 B active per token.
"""
from repro.configs.base import ModelConfig
from repro.configs.registry import register

CONFIG = register(ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
    n_layers=27,
    first_dense=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,                  # the leading dense layer
    vocab_size=163840,
    pattern=(("mla", "moe"),),
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    n_experts=64,
    top_k=6,
    moe_d_ff=1408,
    router="sigmoid",
    routed_scale=2.446,
    router_aux_coef=0.0,
    n_shared_experts=2,
    norm_eps=1e-5,
))
