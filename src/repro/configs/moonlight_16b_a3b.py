"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B, config.json].

DeepSeek-V3's layer at a size whose layers fit one chip whole: multi-head
latent attention (no q compression, kv rank 512, 128 + 64 rope query/key
dims, 128 value dims), one leading dense layer, then 64 routed experts of
width 1408 (top-6) beside 2 shared ones, chosen by a sigmoid router whose
bias picks the experts but not their weights.
"""
from .base import ModelConfig, register

register(ModelConfig(
    name="moonlight-16b-a3b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,                 # moe_intermediate_size
    vocab=163840,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    router_score="sigmoid",    # noaux_tc, n_group = topk_group = 1
    routed_scale=2.446,
    first_dense_layers=1,
    d_ff_dense=11264,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    norm_eps=1e-5,
))
