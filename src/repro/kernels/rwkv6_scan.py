"""Pallas TPU RWKV6 time-mix recurrence (data-dependent decay).

Grid (batch*heads, n_chunks): matrix state S (hd, hd) persists in VMEM
scratch; within a chunk the recurrence is stepped in an unrolled loop
(chunk is small; each step is rank-1 work on the VPU/MXU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
                 chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    hd = r_ref.shape[-1]
    u = u_ref[0].astype(jnp.float32)            # (1, hd) bonus
    ii = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
    eye = ii == jj

    def col(row):
        """(1, hd) row -> (hd, 1) column by a masked lane reduction
        (no transpose of a one-row tile)."""
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    S = s_ref[...]                              # (hd, hd)
    # chunk is small and static: unrolled, so every row slice is static
    for t in range(chunk):
        r = r_ref[0, t:t + 1, :].astype(jnp.float32)   # (1, hd)
        k = k_ref[0, t:t + 1, :].astype(jnp.float32)
        v = v_ref[0, t:t + 1, :].astype(jnp.float32)
        w = w_ref[0, t:t + 1, :].astype(jnp.float32)   # decays in (0,1)
        kv = col(k) * v                                # (hd, hd)
        # r @ (S + u[:, None] * kv) == r @ S + (r . (u * k)) v
        o = r @ S + jnp.sum(r * u * k, axis=1, keepdims=True) * v
        o_ref[0, t:t + 1, :] = o.astype(o_ref.dtype)
        S = col(w) * S + kv
    s_ref[...] = S


def rwkv6_scan(r, k, v, w, u, *, chunk: int = 32,
               interpret: bool = False):
    """r, k, v, w: (BH, S, hd); u: (BH, hd) per-head bonus.
    Returns o: (BH, S, hd)."""
    BH, S, hd = r.shape
    assert S % chunk == 0, "pad sequence to a chunk multiple"
    nc = S // chunk
    kernel = functools.partial(_rwkv_kernel, chunk=chunk)
    o = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u[:, None, :])   # (BH, 1, hd): a full-width tile row
    return o
