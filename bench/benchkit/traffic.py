"""The one general generator: a configuration and a traffic mix, read
from their files, become the sweep a user asks for.

A sweep is a grid of scenarios, trace seeds x loads x policies, on one
host. Its block of trace seeds, as many as the configuration's
``traces_per_sweep``, is drawn from the traffic file's ``block_seed``
by the benchmark's own trace generator (``reftrace``), stratum by
stratum: each stratum asks for ``count`` traces whose invocation count
lies in ``[tasks_min, tasks_max]``. The run's
``--seed`` orders the cells in the batch. Every seed thus gives the
same work in another order: a sweep runs until its slowest cell is
done, so drawing the traces from the run's seed made the work of one
sweep differ by a sixth from seed to seed, far more than two runs of
one seed differ.

``build_scenarios`` is the user's side of the sweep: it asks the
program's own trace generator for each trace, cuts it to the
configuration's horizon and makes one ``Scenario`` per cell.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from . import reftrace

MAX_CANDIDATES = 500


@dataclass(frozen=True)
class CellPlan:
    trace_seed: int
    load: float
    policy: str
    kw: dict = field(default_factory=dict, hash=False, compare=False)


@dataclass
class Plan:
    cells: list
    # trace seed -> the benchmark's own trace (arrays), cut to the horizon
    traces: dict


def trace_knobs(config: dict) -> dict:
    return {k: config[k] for k in reftrace.TRACE_DEFAULTS}


def reference_trace(config: dict, trace_seed: int) -> dict:
    tr = reftrace.trace(trace_seed, **trace_knobs(config))
    return reftrace.cut(tr, config["horizon_s"])


def plan(config: dict, traffic: dict, seed: int,
         block_seed=None) -> Plan:
    """The sweep of one run: the traces of ``block_seed`` (the traffic
    file's when None) in the cell order ``seed`` draws."""
    rng = np.random.default_rng(traffic["block_seed"]
                                if block_seed is None else block_seed)
    strata = traffic["trace_seeds"]
    need = [s["count"] for s in strata]
    if sum(need) != config["traces_per_sweep"]:
        raise ValueError(f"the traffic's strata ask for {sum(need)} traces; "
                         f"the configuration sweeps "
                         f"{config['traces_per_sweep']}")
    chosen = [[] for _ in strata]
    traces = {}
    for _ in range(MAX_CANDIDATES):
        if not any(need):
            break
        s = int(rng.integers(0, 2 ** 31 - 1))
        if s in traces:
            continue
        tr = reference_trace(config, s)
        n = len(tr["arrival"])
        for i, st in enumerate(strata):
            if need[i] and st["tasks_min"] <= n <= st["tasks_max"]:
                need[i] -= 1
                chosen[i].append(s)
                traces[s] = tr
                break
    else:
        raise RuntimeError(f"no {need} trace seeds in their strata after "
                           f"{MAX_CANDIDATES} candidates")
    policies = config["policies"]
    cells = [CellPlan(trace_seed=s, load=float(load), policy=p,
                      kw=dict(policies[p]))
             for group in chosen for s in group
             for load in traffic["loads"] for p in traffic["policies"]]
    order = np.random.default_rng(seed).permutation(len(cells))
    return Plan(cells=[cells[i] for i in order], traces=traces)


def cell_traffic(plan_: Plan, cell: CellPlan) -> dict:
    """The invocations one cell must simulate: its trace at its load."""
    return reftrace.scale_load(plan_.traces[cell.trace_seed], cell.load)


def build_scenarios(config: dict, plan_: Plan) -> list:
    """The sweep's scenarios, built by the program's own generator."""
    from repro import FleetSpec, PolicySpec, Scenario, WorkloadSpec
    from repro.traces.azure import TraceSpec
    from repro.traces.workload import generate_workload

    knobs = trace_knobs(config)
    horizon_ms = None if config["horizon_s"] is None \
        else config["horizon_s"] * 1000.0
    tasks = {}
    for s in plan_.traces:
        w = generate_workload(TraceSpec(seed=s, **knobs)).tasks
        tasks[s] = w if horizon_ms is None else \
            [t for t in w if t.arrival < horizon_ms]
    fleet = FleetSpec(cores_per_node=config["cores_per_node"])
    return [Scenario(workload=WorkloadSpec(kind="tasks",
                                           tasks=tasks[c.trace_seed],
                                           load_scale=c.load),
                     fleet=fleet,
                     policy=PolicySpec(name=c.policy, kw=dict(c.kw)))
            for c in plan_.cells]
