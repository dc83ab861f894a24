"""Batched Monte-Carlo engine: whole sweep grids in one JAX program.

Public surface (DESIGN.md Sec. 16):

* :class:`MonteCarlo` / :class:`MonteCarloResult` — Scenario-level
  (seeds x loads) grids, ``MonteCarlo(sc, seeds=..., loads=...)``;
* :func:`run_scenarios` — batch a list of in-regime scenarios;
* :func:`supported` — the regime gate (None = batched, str = reason
  for scalar fallback).

Equivalence contract: the engine runs under x64 (entered per call via
``jax.enable_x64(True)`` — the repo's global dtype default is
untouched). On the CPU backend it reproduces the scalar engine's
per-task digests and every cost roll-up bit-for-bit. On the TPU, which
emulates float64 as double-f32, integer observables are exact and
floats agree within ``kernels.float_rtol`` (DESIGN.md Sec. 16).

Heavy imports (jax) are deferred until first use so ``import repro``
stays light.
"""
from __future__ import annotations

_EXPORTS = {
    "MonteCarlo": ("repro.mc.montecarlo", "MonteCarlo"),
    "MonteCarloResult": ("repro.mc.montecarlo", "MonteCarloResult"),
    "run_scenarios": ("repro.mc.engine", "run_scenarios"),
    "supported": ("repro.mc.dispatch", "supported"),
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    try:
        mod_name, attr = _EXPORTS[name]
    except KeyError:
        raise AttributeError(f"module 'repro.mc' has no attribute "
                             f"{name!r}") from None
    import importlib
    return getattr(importlib.import_module(mod_name), attr)
