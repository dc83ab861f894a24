"""``MonteCarlo``: one Scenario, a grid of (seeds x loads), one launch.

The Scenario-level front door of the batched engine (DESIGN.md
Sec. 16): take a base scenario, cross it with trace seeds and load
scales, advance every resulting cell in a single vmapped device
program, and return per-cell summary rows ready for the sweep/bench/
gate toolchain.  Cells the batched regime cannot reproduce bit-for-bit
fall back to the scalar engine transparently (``meta["fallback"]``
counts them).

    mc = MonteCarlo(scenario, seeds=range(32), loads=(0.5, 1.0, 2.0))
    rows = mc.run().rows          # 96 cells, one compiled program
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import TYPE_CHECKING, Optional, Sequence

from ..traces.azure import TraceSpec

if TYPE_CHECKING:
    from ..scenario import Scenario, ScenarioResult


@dataclass
class MonteCarloResult:
    results: list["ScenarioResult"]
    seeds: tuple
    loads: tuple
    meta: dict = field(default_factory=dict)

    @property
    def rows(self) -> list[dict]:
        cells = [(seed, load) for seed in self.seeds for load in self.loads]
        return [{"seed": seed, "load_scale": load, **r.summary()}
                for (seed, load), r in zip(cells, self.results)]


@dataclass
class MonteCarlo:
    """Expand ``scenario`` over ``seeds`` x ``loads`` and run the grid.

    ``seeds`` re-seed the workload's :class:`TraceSpec` (the workload
    must be trace-driven — ``azure``/``synthetic``); ``loads``
    override ``WorkloadSpec.load_scale``.  ``backend="jax"`` uses the
    batched engine wherever :func:`repro.mc.dispatch.supported`
    allows and the scalar engine elsewhere; ``backend="python"``
    forces the scalar engine everywhere (the equivalence baseline).
    """

    scenario: "Scenario"
    seeds: Sequence[int] = (0,)
    loads: Sequence[float] = (1.0,)
    backend: str = "jax"

    def cells(self) -> list["Scenario"]:
        wl = self.scenario.workload
        if wl.kind not in ("azure", "synthetic"):
            raise ValueError("MonteCarlo needs a trace-driven workload "
                             "(kind='azure') to re-seed")
        base_trace = wl.trace or TraceSpec()
        out = []
        for seed in self.seeds:
            trace = replace(base_trace, seed=seed)
            for load in self.loads:
                out.append(replace(
                    self.scenario,
                    workload=replace(wl, trace=trace, load_scale=load)))
        return out

    def run(self) -> MonteCarloResult:
        from ..scenario import run as run_scalar
        from .engine import route, run_scenarios

        cells = self.cells()
        if self.backend == "jax":
            reasons, built = route(cells)
        elif self.backend == "python":
            reasons, built = ["forced"] * len(cells), {}
        else:
            raise ValueError(f"unknown backend {self.backend!r}")

        results: list[Optional["ScenarioResult"]] = [None] * len(cells)
        dev = sorted(built)
        if dev:
            batched = run_scenarios([cells[k] for k in dev],
                                    prebuilt=[built[k] for k in dev])
            for k, res in zip(dev, batched):
                results[k] = res
        for k, sc in enumerate(cells):
            if results[k] is None:
                results[k] = run_scalar(sc)
                if reasons[k] != "forced":
                    results[k].fallback_reason = reasons[k]

        backends = [r.backend for r in results]
        counts: dict[str, int] = {}
        for why in reasons:
            if why is not None:
                counts[why] = counts.get(why, 0) + 1
        return MonteCarloResult(
            results=results, seeds=tuple(self.seeds),
            loads=tuple(self.loads),
            meta={"backends": backends,
                  "fallback": backends.count("python"),
                  "fallback_reasons": counts})
