"""moonlight-16b-a3b [mla + moe] — the DeepSeek-V3 block: 27L d_model=2048,
16 latent-attention heads (128 + 64 query/key, 128 value, KV rank 512, no
query compression), layer 0 a dense SwiGLU of 11264, then 64 routed experts
of 1408 (sigmoid router, top-6, bias-corrected selection, scale 2.446) and 2
shared experts; vocab 163840, context 8192.
[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json]"""
from repro.configs.base import MLAMoEConfig

CONFIG = MLAMoEConfig(
    name="moonlight-16b-a3b",
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json",
    num_layers=27,
    first_dense=1,
    d_model=2048,
    num_heads=16,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    kv_lora_rank=512,
    d_ff=11264,
    d_expert=1408,
    num_shared=2,
    num_experts=64,
    experts_per_token=6,
    experts_held=64,
    routed_scale=2.446,
    rope_theta=50000.0,
    eps=1e-5,
    vocab_size=163840,
    max_position=8192,
)
