"""repro.kernels — Pallas TPU kernels (+ jnp oracles in ref.py).

Checked against the oracles in interpret mode on the CPU and compiled
for a v5e chip (``tests/test_tpu_compile.py``); BlockSpecs sized for
v5e VMEM.
"""
from . import ref
from .ops import (decode_attention, flash_attention, fused_rmsnorm,
                  rwkv6_scan, ssm_scan)

__all__ = ["ref", "decode_attention", "flash_attention", "fused_rmsnorm",
           "rwkv6_scan", "ssm_scan"]
