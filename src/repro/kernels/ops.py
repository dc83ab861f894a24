"""jit'd public wrappers for the Pallas kernels.

Every wrapper lowers for the TPU unless the caller passes
``interpret=True`` (the Pallas interpreter, for CPU tests and CPU
calibration); nothing picks interpret mode on its own.
"""
from __future__ import annotations

from functools import partial

import jax

from . import ref
from .decode_attention import decode_attention as _decode
from .flash_attention import flash_attention as _flash
from .fused_rmsnorm import fused_rmsnorm as _rmsnorm
from .rwkv6_scan import rwkv6_scan as _rwkv
from .ssm_scan import ssm_scan as _ssm


@partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                   "k_block", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, q_block=512,
                    k_block=512, interpret=False):
    return _flash(q, k, v, causal=causal, window=window, q_block=q_block,
                  k_block=k_block, interpret=interpret)


@partial(jax.jit, static_argnames=("window", "k_block", "interpret"))
def decode_attention(q, k, v, lengths, *, window=0, k_block=512,
                     interpret=False):
    return _decode(q, k, v, lengths, window=window, k_block=k_block,
                   interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(xbar, B, C, cumlog, *, chunk=64, interpret=False):
    return _ssm(xbar, B, C, cumlog, chunk=chunk,
                interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, w, u, *, chunk=32, interpret=False):
    return _rwkv(r, k, v, w, u, chunk=chunk,
                 interpret=interpret)


@partial(jax.jit, static_argnames=("eps", "rows", "interpret"))
def fused_rmsnorm(x, w, *, eps=1e-6, rows=256, interpret=False):
    return _rmsnorm(x, w, eps=eps, rows=rows,
                    interpret=interpret)
