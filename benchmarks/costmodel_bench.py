"""Cost-model calibration benchmark: predictor accuracy + the $ delta
the learned model actually moves end-to-end.

Two halves, one artifact:

1. **Per-op accuracy rows** — run the calibration pipeline
   (``repro.costmodel.calibrate``) and emit one row per calibration op
   with its measured vs predicted latency and absolute percentage
   error. ``--smoke`` (or ``CLUSTER_BENCH_SMOKE``) uses the synthetic
   mode (frozen row table + hidden roofline — deterministic, jax-free);
   the full tier compiles and replays the real Pallas kernels, and a
   kernel that fails to compile or run fails the bench. The ``mode``
   field keys the gate cell, so synthetic and measured trajectories
   never cross-compare.
2. **End-to-end $ delta** — the same small llm-FaaS scenario run twice
   through ``repro.run``, once with the static cost model and once with
   the learned one (cost-aware dispatch seeded by the calibrated
   queueing prior, ``max_load="auto"`` admission ceiling, EWMA
   pre-warm). Folded into the first row as ``headline_*`` fields.

Headline: calibration MAPE must clear the mode's bound — 0.25 for the
synthetic tier (the acceptance bound: a controlled experiment whose
ground truth IS linear in the features), 0.50 for compile-and-replay
(Pallas kernel bodies hide their FLOPs inside custom calls, so the
roofline fit on a CPU host is diagnostic; per-op APE *drift* is the
gated quantity there). Exit 1 past the bound. Emits
``results/benchmarks/BENCH_costmodel.json``;
registered as ``costmodel`` in ``benchmarks.run``; gated by
``benchmarks.regression_gate`` (kind ``costmodel``: a shared op's APE
must not grow by more than the threshold, absolute).

Standalone: ``python -m benchmarks.costmodel_bench [--smoke]``.
"""
from __future__ import annotations

import json
import os
import sys

from repro.costmodel.calibrate import calibrate
from repro.scenario import (FleetSpec, PolicySpec, ResilienceSpec,
                            Scenario, WorkloadSpec, run)
from repro.serving.llm import LLMSpec
from repro.traces import TraceSpec

from .common import RESULTS

MAPE_BOUND = 0.25          # synthetic tier: the acceptance bound
MEASURE_MAPE_BOUND = 0.50  # compile-and-replay tier: diagnostic bound
MODEL = "deepseek-7b"


def _calibrate(smoke: bool) -> dict:
    if smoke:
        return calibrate(mode="synthetic", seed=0)
    return calibrate(mode="measure", repeats=5, small=True)


def _op_rows(artifact: dict) -> list[dict]:
    rows = []
    for r in artifact["rows"]:
        ape = abs(r["predicted_ms"] - r["measured_ms"]) / r["measured_ms"] \
            if r["measured_ms"] > 0 else 0.0
        rows.append({
            "op": r["op"],
            # mode keys the gate cell: a synthetic trajectory must
            # never gate against a measured one.
            "mode": artifact["mode"],
            "flops": r["flops"],
            "bytes": r["bytes"],
            "measured_ms": r["measured_ms"],
            "predicted_ms": r["predicted_ms"],
            "ape": ape,
            "mape": artifact["mape"],
        })
    return rows


def _scenario(cost_model) -> Scenario:
    return Scenario(
        workload=WorkloadSpec(
            kind="llm",
            trace=TraceSpec(minutes=1, invocations_per_min=120.0,
                            n_functions=8, seed=11),
            llm=LLMSpec(model=MODEL)),
        fleet=FleetSpec(n_nodes=2, cores_per_node=4,
                        dispatcher="cost_aware", seed=3),
        policy=PolicySpec(name="hybrid"),
        resilience=ResilienceSpec(
            admission={"max_load": "auto", "overload_action": "queue"}),
        cost_model=cost_model)


def _e2e_delta(artifact: dict) -> dict:
    static = run(_scenario(None)).summary()
    learned = run(_scenario(dict(artifact))).summary()
    return {
        "static_total_cost_usd": static["total_cost_usd"],
        "learned_total_cost_usd": learned["total_cost_usd"],
        "usd_delta": learned["total_cost_usd"] - static["total_cost_usd"],
        "learned_cost_coeff": learned["cost_coeff"],
        "learned_cost_obs": learned["cost_obs"],
    }


def costmodel_matrix(smoke: bool = None) -> list[dict]:
    if smoke is None:
        smoke = bool(os.environ.get("CLUSTER_BENCH_SMOKE"))
    artifact = _calibrate(smoke)
    rows = _op_rows(artifact)
    bound = MAPE_BOUND if artifact["mode"] == "synthetic" \
        else MEASURE_MAPE_BOUND
    head = {
        "mape": artifact["mape"],
        "mape_bound": bound,
        "mape_ok": artifact["mape"] <= bound,
        "queue_ms_per_load": artifact["queue_ms_per_load"],
    }
    head.update(_e2e_delta(artifact))
    rows[0] = {**rows[0], **{f"headline_{k}": v for k, v in head.items()}}
    return rows


COLS = ("op", "mode", "measured_ms", "predicted_ms", "ape")


def main() -> None:
    from repro.cluster.sweep import print_rows
    smoke = "--smoke" in sys.argv
    rows = costmodel_matrix(smoke=smoke)
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "BENCH_costmodel.json").write_text(
        json.dumps({"matrix": rows}, indent=2))
    print_rows(rows, COLS)
    first = rows[0]
    print(f"# costmodel {first['mode']}: mape={first['headline_mape']:.4f} "
          f"(bound {first['headline_mape_bound']}); learned-vs-static "
          f"${first['headline_usd_delta']:+.6f} total on the llm cell "
          f"(coeff={first['headline_learned_cost_coeff']:.1f} after "
          f"{first['headline_learned_cost_obs']} observations)",
          file=sys.stderr)
    if not first["headline_mape_ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
