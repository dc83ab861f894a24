"""Pallas TPU Mamba2 (SSD) chunked selective scan.

Grid (batch*heads, n_chunks): the recurrent state h (hd, ds) persists in
VMEM scratch across sequential chunk steps; within a chunk the
intra-chunk term is the quadratic (Q,Q) decay-masked form (MXU work),
matching the chunk math of ``ref.ssm_scan_ref`` exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(xb_ref, b_ref, c_ref, cum_ref, y_ref, h_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xb = xb_ref[0].astype(jnp.float32)          # (Q, hd)
    B = b_ref[0].astype(jnp.float32)            # (Q, ds)
    C = c_ref[0].astype(jnp.float32)            # (Q, ds)
    cum_r = cum_ref[0, 0].astype(jnp.float32)   # (1, Q) within-chunk cumsum
    tot = cum_r[:, chunk - 1:]                  # (1, 1)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # the same values as a column, by a masked lane reduction (no
    # transpose of a one-row tile)
    cum_c = jnp.sum(jnp.where(ii == jj, cum_r, 0.0), axis=1,
                    keepdims=True)              # (Q, 1)
    # intra-chunk: L[i,j] = exp(cum_i - cum_j), j <= i
    L = jnp.where(jj <= ii, jnp.exp(cum_c - cum_r), 0.0)
    sBB = C @ B.T                                # (Q, Q)
    y_intra = (sBB * L) @ xb                     # (Q, hd)
    # inter-chunk: state contribution decayed to each position
    h = h_ref[...]                               # (hd, ds)
    y_inter = jnp.exp(cum_c) * (C @ h.T)         # (Q, hd)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)
    # state update
    decay_to_end = jnp.exp(tot - cum_c)          # (Q, 1)
    h_ref[...] = h * jnp.exp(tot) + \
        (xb * decay_to_end).T @ B                # (hd, ds)


def ssm_scan(xbar, B, C, cumlog, *, chunk: int = 64,
             interpret: bool = False):
    """xbar: (BH, S, hd) dt-weighted inputs; B, C: (BH, S, ds);
    cumlog: (BH, S) per-chunk-reset cumulative log-decay.
    Returns y: (BH, S, hd).

    NOTE: cumlog must already be reset at chunk boundaries
    (cumsum within each chunk), matching the ref oracle.
    """
    BH, S, hd = xbar.shape
    ds = B.shape[-1]
    assert S % chunk == 0, "pad sequence to a chunk multiple"
    nc = S // chunk
    kernel = functools.partial(_ssm_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), xbar.dtype),
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        interpret=interpret,
    )(xbar, B, C, cumlog.reshape(BH, nc, 1, chunk))  # one full row per chunk
    return y
