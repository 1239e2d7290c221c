"""lfm2-8b-a1b [hybrid moe]: 24L d_model=2048, 18 gated short-conv layers
(kernel 3) beside 6 GQA attention layers (32H, kv=8, head_dim 64, QK-norm,
RoPE 1e6), dense SwiGLU 7168 in layers 0-1, then 32 experts of width 1792,
top-4 by sigmoid + expert bias; vocab=65536, tied embedding.
[hf:LiquidAI/LFM2-8B-A1B config.json]

The layer kinds follow no period, so the pattern is all 24 layers, one
unit. The experts run dropless (``moe_dropless``). ``CONFIG`` holds every
expert; the benchmark's deployment shares each MoE layer over 4 chips
(``expert_shards=4``), and a chip holds 8 of the 32.
"""
from repro.models.config import ModelConfig

LAYER_TYPES = ("conv", "conv", "attn", "conv", "conv", "conv", "attn",
               "conv", "conv", "conv", "attn", "conv", "conv", "conv",
               "attn", "conv", "conv", "conv", "attn", "conv", "conv",
               "attn", "conv", "conv")

CONFIG = ModelConfig(
    name="lfm2-8b-a1b", family="hybrid",
    n_layers=24, d_model=2048, n_heads=32, n_kv_heads=8,
    d_ff=7168, moe_d_ff=1792, vocab_size=65536, pattern=LAYER_TYPES,
    rope_theta=1e6, norm_eps=1e-5, qk_norm=True, conv_kernel=3,
    n_experts=32, experts_per_token=4, n_dense_layers=2,
    router="sigmoid_bias", moe_dropless=True,
)
SMOKE = ModelConfig(
    name="lfm2-8b-a1b-smoke", family="hybrid",
    n_layers=6, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, moe_d_ff=32, vocab_size=256,
    pattern=("conv", "conv", "attn", "conv", "attn", "conv"),
    rope_theta=1e6, norm_eps=1e-5, qk_norm=True, conv_kernel=3,
    n_experts=8, experts_per_token=2, n_dense_layers=2,
    router="sigmoid_bias", moe_dropless=True,
)
