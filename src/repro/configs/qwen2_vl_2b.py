"""qwen2-vl-2b [vlm]: 28L d=1536 12H (GQA kv=2) d_ff=8960, vocab 151936,
M-RoPE + dynamic resolution (ViT frontend is a STUB per the assignment;
input_specs() supplies precomputed patch embeddings).  [arXiv:2409.12191]
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="qwen2-vl-2b", family="vlm",
    n_layers=28, d_model=1536, n_heads=12, n_kv_heads=2,
    d_ff=8960, vocab=151936, tie_embeddings=True,
    ms_per_token_decode=2.5, ms_per_ktoken_prefill=7.0,
)
