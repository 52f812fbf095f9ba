"""moonlight-16b-a3b [mla]: the DeepSeek-V3 block at d_model=2048
(hf:moonshotai/Moonlight-16B-A3B, ``model_type`` deepseek_v3): 27 layers,
16 heads of multi-head latent attention with no query low-rank (latent
512, nope/rope/v head dims 128/64/128), one leading dense layer of width
11264, then 64 routed experts of width 1408, top-6, 2 shared, under a
sigmoid router whose per-expert correction bias steers selection only
(``noaux_tc``, one group), gates renormalised and scaled by 2.446."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="mla",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab_size=163840,
    rope_theta=50000.0, norm_eps=1e-5,
    n_experts=64, experts_per_token=6, n_shared_experts=2, moe_d_ff=1408,
    router_scoring="sigmoid", routed_scaling=2.446,
    n_dense_layers=1,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
)
