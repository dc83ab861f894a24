"""granite-moe-3b-a800m [moe]: 32L d=1536 24H (GQA kv=8) d_ff=512/expert,
vocab 49155, 40 experts top-8.  [hf:ibm-granite/granite-3.0-1b-a400m-base]

Sharding notes: 24 heads, 40 experts, vocab 49155 are all non-divisible
by the 16-way model axis -> resolver falls back to replicated heads
(+ sequence-sharded KV), expert-TP on d_ff (512/16), embed-dim-sharded
vocab table (DESIGN.md Sec. 6).
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="granite-moe-3b-a800m", family="moe",
    n_layers=32, d_model=1536, n_heads=24, n_kv_heads=8,
    d_ff=512, vocab=49155, n_experts=40, top_k=8, tie_embeddings=True,
    ms_per_token_decode=3.0, ms_per_ktoken_prefill=9.0,
)
