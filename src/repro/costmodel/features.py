"""Per-op feature extraction for the learned cost model.

Two sources, one row schema:

* :func:`kernel_features` — the REAL Pallas kernels (``kernels.ops``):
  each op is lowered and compiled via the standard jax path
  (``jax.jit(...).lower(...).compile()``) and its optimized HLO text
  is folded through ``hlo_analysis.analyze`` into FLOP / byte / trip-count
  features. This is the compile side of compile-and-replay: the same
  compiled executable the calibrator later times.
* :func:`llm_chunk_features` — the ``serving.llm`` chunk shapes,
  derived analytically (2·params FLOPs per token, weights + KV bytes
  per step) so the llm consumer works without jax in the process.

Row schema (``FEATURE_KEYS``): ``op`` (label), ``flops``, ``bytes``,
``trips`` (kernel grid / while-loop trip count where known, else 1),
``tokens`` (llm rows), plus pass-through shape metadata. The calibrator
fits latency on (1, gflops, mbytes) — scaled so the normal equations
stay well-conditioned in float64.
"""
from __future__ import annotations

from typing import Optional

FEATURE_KEYS = ("op", "flops", "bytes", "trips")

# Feature scaling used everywhere a predictor touches a row: raw FLOP /
# byte counts are ~1e9 / ~1e6 and would wreck the normal equations.
GFLOP = 1e9
MBYTE = 1e6


def feature_vector(row: dict) -> tuple:
    """(1.0, gflops, mbytes) — THE predictor input for a feature row."""
    return (1.0, float(row["flops"]) / GFLOP, float(row["bytes"]) / MBYTE)


# -- analytic llm chunk features (jax-free) ---------------------------------

def llm_chunk_features(cfg, seq_len: int = 4096,
                       prefill_tokens: int = 1024) -> list[dict]:
    """Feature rows for the two ``serving.llm`` chunk kinds.

    Dense-equivalent FLOPs: 2·params per token (the standard inference
    estimate); bytes: one full weight read plus the KV the step
    touches. MoE checkpoints ship every expert but activate
    ``n_active``/``n_experts`` of the MLP share — the analytic model
    follows the same approximation ``approx_param_bytes`` uses.
    """
    from ..serving.llm import BYTES_PER_PARAM, approx_param_bytes
    from ..serving.request import kv_bytes

    param_bytes = approx_param_bytes(cfg)
    params = param_bytes / BYTES_PER_PARAM
    n_exp = max(getattr(cfg, "n_experts", 0), 1)
    top_k = max(getattr(cfg, "top_k", 0), 1) if n_exp > 1 else 1
    active = params * (top_k / n_exp) if n_exp > 1 else params
    rows = [
        {
            "op": "llm_prefill",
            "tokens": prefill_tokens,
            "flops": 2.0 * active * prefill_tokens,
            "bytes": param_bytes + kv_bytes(cfg, prefill_tokens),
            "trips": max(1, prefill_tokens // 512),
        },
        {
            "op": "llm_decode",
            "tokens": 1,
            "flops": 2.0 * active,
            "bytes": param_bytes + kv_bytes(cfg, seq_len),
            "trips": 1,
        },
    ]
    return rows


# -- compiled kernel features (jax-gated) -----------------------------------

def _kernel_cases(small: bool = True) -> list[tuple]:
    """(name, builder) pairs; builder() -> (fn, args) ready to lower.
    Shapes are deliberately small: CPU interpret-mode Pallas is slow,
    and the predictor extrapolates on FLOPs/bytes, not on shape."""
    import jax.numpy as jnp
    import numpy as np

    from ..kernels import ops

    BH, S, hd, ds = (2, 128, 64, 16) if small else (4, 512, 64, 16)

    def _r(shape, seed):
        rng = np.random.default_rng(seed)
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32))

    def flash():
        q, k, v = _r((BH, S, hd), 0), _r((BH, S, hd), 1), _r((BH, S, hd), 2)
        return ops.flash_attention, (q, k, v)

    def decode():
        q = _r((BH, 1, hd), 3)
        k, v = _r((BH, S, hd), 4), _r((BH, S, hd), 5)
        lengths = jnp.full((BH,), S, jnp.int32)
        return ops.decode_attention, (q, k, v, lengths)

    def ssm():
        xbar = _r((BH, S, hd), 6)
        B, C = _r((BH, S, ds), 7), _r((BH, S, ds), 8)
        cumlog = jnp.cumsum(-jnp.abs(_r((BH, S), 9)) * 0.01, axis=-1)
        return ops.ssm_scan, (xbar, B, C, cumlog)

    def rwkv():
        r, k, v = _r((BH, S, hd), 10), _r((BH, S, hd), 11), _r((BH, S, hd), 12)
        w = -jnp.abs(_r((BH, S, hd), 13)) * 0.1
        u = _r((BH, hd), 14)
        return ops.rwkv6_scan, (r, k, v, w, u)

    def rmsnorm():
        x, w = _r((S * BH, 4 * hd), 15), _r((4 * hd,), 16)
        return ops.fused_rmsnorm, (x, w)

    return [("flash_attention", flash), ("decode_attention", decode),
            ("ssm_scan", ssm), ("rwkv6_scan", rwkv),
            ("fused_rmsnorm", rmsnorm)]


def compile_kernel(name: str, builder, *, interpret: bool):
    """Lower + compile one kernel case; returns ``(compiled, args)``.
    The compiled executable serves both sides of compile-and-replay:
    ``analyze(compiled.as_text())`` for features, timed invocation for
    the calibrator's measurements. ``interpret`` selects the Pallas
    interpreter (CPU hosts) over the TPU lowering."""
    fn, args = builder()
    return fn.lower(*args, interpret=interpret).compile(), args


def kernel_features(small: bool = True, ops_filter: Optional[list] = None,
                    ) -> list[dict]:
    """FLOP/byte/trip rows for the compiled Pallas kernels.

    Requires jax; raises ImportError where it is absent (callers gate —
    the synthetic calibration path needs no compiler at all). Off the
    TPU the kernels run in the Pallas interpreter.
    """
    import jax

    from .hlo_analysis import analyze

    interpret = jax.default_backend() != "tpu"
    rows = []
    for name, builder in _kernel_cases(small):
        if ops_filter is not None and name not in ops_filter:
            continue
        compiled, args = compile_kernel(name, builder, interpret=interpret)
        a = analyze(compiled.as_text())
        rows.append({
            "op": name,
            "flops": float(a["flops"]),
            "bytes": float(a["bytes"]),
            "trips": int(a.get("n_computations", 1)) or 1,
        })
    return rows
