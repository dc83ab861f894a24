"""Serving-gateway benchmark: the paper's technique on model serving,
per catalog architecture."""
from __future__ import annotations

from repro.configs import ARCHS, get_config
from repro.scenario import (FleetSpec, PolicySpec, Scenario, ServingSpec,
                            WorkloadSpec, run)
from repro.serving import requests_from_trace
from repro.traces import TraceSpec

GATEWAY_TRACE = TraceSpec(minutes=1, invocations_per_min=6000,
                          n_functions=120, seed=11)  # overload regime


def _gateway(cfg, policy, reqs):
    """One-big-node serving scenario — the historical run_gateway
    defaults (50 slots, 25 FIFO, 95th-pct adaptation, rightsizing)."""
    return run(Scenario(
        workload=WorkloadSpec(kind="tasks", tasks=reqs),
        fleet=FleetSpec(cores_per_node=50),
        policy=PolicySpec(name=policy, adapt_pct=95.0, rightsize=True,
                          n_fifo=25 if policy == "hybrid" else None,
                          serving=ServingSpec(model=cfg)))).raw


def serving_gateway():
    """Hybrid vs CFS-analogue vs FIFO per architecture (billing +
    p99s). The savings follow the per-arch preemption cost: SSM archs
    (cheap state swaps) vs long-KV dense archs."""
    rows = []
    for arch in ARCHS:
        cfg = get_config(arch)
        reqs = requests_from_trace(cfg, GATEWAY_TRACE)
        out = {}
        for policy in ("fifo", "cfs", "hybrid"):
            out[policy] = _gateway(cfg, policy, reqs)
        rows.append({
            "arch": arch,
            "cost_fifo": out["fifo"].cost_usd(),
            "cost_cfs": out["cfs"].cost_usd(),
            "cost_hybrid": out["hybrid"].cost_usd(),
            "saving_vs_cfs":
                out["cfs"].cost_usd() / max(out["hybrid"].cost_usd(),
                                            1e-12),
            "p99_exec_hybrid_s": out["hybrid"].p("execution", 99) / 1e3,
            "p99_resp_hybrid_s": out["hybrid"].p("response", 99) / 1e3,
        })
    rows.sort(key=lambda r: -r["saving_vs_cfs"])
    rows.insert(0, {"arch": "best", "value": rows[0]["saving_vs_cfs"]})
    return rows
