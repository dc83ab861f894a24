"""Pallas TPU flash-decode: single-query attention over a long KV cache.

Grid (batch*heads, n_k_blocks): each step combines one KV block into a
running (m, l, acc) partial-softmax state in VMEM scratch — the classic
flash-decode block-parallel reduction, laid out sequentially per TPU
core. Per-row valid lengths (cache fill levels) are passed as a scalar
array, prefetched into SMEM once for the whole grid, and masked inside
the kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   k_block: int, nk: int, scale: float, window: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (1, hd)
    k = k_ref[0].astype(jnp.float32)                    # (kb, hd)
    v = v_ref[0].astype(jnp.float32)
    s = q @ k.T                                         # (1, kb)
    pos = len_ref[pl.program_id(0)] - 1                 # current position
    k_idx = ki * k_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_idx <= pos
    if window > 0:
        mask &= k_idx > pos - window
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]                                 # (1, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                              # (1, kb)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q, k, v, lengths, *, k_block: int = 512,
                     window: int = 0, interpret: bool = False):
    """q: (BH, 1, hd); k, v: (BH, S, hd); lengths: (BH,) int32 — number
    of valid cache entries per row. Returns (BH, 1, hd)."""
    BH, _, hd = q.shape
    S = k.shape[1]
    k_block = min(k_block, S)
    nk = -(-S // k_block)
    if nk * k_block != S:
        pad = nk * k_block - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_decode_kernel, k_block=k_block, nk=nk,
                               scale=scale, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, nk),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, j, lens: (b, 0, 0)),
            pl.BlockSpec((1, k_block, hd), lambda b, j, lens: (b, j, 0)),
            pl.BlockSpec((1, k_block, hd), lambda b, j, lens: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, j, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, 1, hd), q.dtype),
        interpret=interpret,
    )(lengths, q, k, v)
    return out
