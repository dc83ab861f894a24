"""repro — hybrid two-level FaaS scheduling (Zhao et al., 2024): a
scalar discrete-event scheduler simulator, a batched Monte-Carlo engine
that runs its sweeps on a TPU, fleets, and an LLM-as-FaaS workload
priced through the scheduler. See DESIGN.md.

The public entrypoint is the Scenario API::

    import repro
    sc = repro.Scenario(...)
    res = repro.run(sc)
    print(res.summary())

Scenario machinery is imported lazily so that ``import repro`` stays
dependency-free (the batched engine pulls in JAX only when a
scenario actually needs it).
"""
__version__ = "1.0.0"

_SCENARIO_EXPORTS = (
    "run", "Scenario", "ScenarioResult", "WorkloadSpec", "FleetSpec",
    "PolicySpec", "ServingSpec", "ResilienceSpec",
    "SCHEMA_VERSION", "SUMMARY_KEYS_V1",
)

# Batched Monte-Carlo front door (imports JAX only when touched).
_MC_EXPORTS = ("MonteCarlo", "MonteCarloResult")

# Cost-model substrate (DESIGN.md Sec. 18): pricing + learned models.
_COSTMODEL_EXPORTS = ("PricingSpec", "CostModel", "StaticCostModel",
                      "LearnedCostModel", "make_cost_model")

__all__ = ["__version__", *_SCENARIO_EXPORTS, *_MC_EXPORTS,
           *_COSTMODEL_EXPORTS]


def __getattr__(name):
    if name in _SCENARIO_EXPORTS:
        from . import scenario
        return getattr(scenario, name)
    if name in _MC_EXPORTS:
        from . import mc
        return getattr(mc, name)
    if name in _COSTMODEL_EXPORTS:
        from . import costmodel
        return getattr(costmodel, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_SCENARIO_EXPORTS)
                  | set(_MC_EXPORTS) | set(_COSTMODEL_EXPORTS))
