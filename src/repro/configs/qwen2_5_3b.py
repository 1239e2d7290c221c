"""qwen2.5-3b [dense]: 36L d_model=2048 16H (GQA kv=2) d_ff=11008
vocab=151936 - GQA, QKV bias. [hf:Qwen/Qwen2.5-3B; hf]"""
from repro.models.config import ModelConfig, reduced

CONFIG = ModelConfig(
    name="qwen2.5-3b", family="dense",
    n_layers=36, d_model=2048, n_heads=16, n_kv_heads=2,
    d_ff=11008, vocab_size=151936, qkv_bias=True,
    rope_theta=1e6, pattern=("attn",),
)
SMOKE = reduced(CONFIG)
