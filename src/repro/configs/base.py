"""The published-model configuration record.

Every catalog architecture provides CONFIG, its published widths. The
LLM-as-FaaS workload (``serving``) and the cost model read them.
"""
from __future__ import annotations

from dataclasses import dataclass, replace


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int                # 0 => attention-free
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0           # 0 => d_model // n_heads
    # MoE
    n_experts: int = 0
    top_k: int = 0
    # local/global attention pattern (gemma3): ratio L local : 1 global
    local_window: int = 0
    local_global_ratio: int = 0
    # hybrid (zamba2): shared attention block every k SSM layers
    shared_attn_every: int = 0
    # SSM
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    # RWKV
    rwkv_head_dim: int = 64
    # misc
    tie_embeddings: bool = True
    # serving-model parameters (L2 gateway service-time model)
    ms_per_token_decode: float = 8.0
    ms_per_ktoken_prefill: float = 30.0

    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)
