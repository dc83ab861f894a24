"""Benchmark runner: one entry per paper table/figure + serving gateway.

Prints ``name,us_per_call,derived`` CSV; full rows land in
results/benchmarks/*.json.
"""
from __future__ import annotations

import sys

from . import cluster_bench as C
from . import costmodel_bench as CM
from . import paper_figures as F
from . import llm_faas_bench as L
from . import resilience_bench as R
from . import topology_bench as T
from . import serving_bench as S
from .common import emit, timed

BENCHES = [
    ("fig01_cost_fifo_cfs", F.fig01_cost_fifo_cfs),
    ("fig04_fifo_vs_cfs", F.fig04_fifo_vs_cfs),
    ("fig05_fifo_preempt", F.fig05_fifo_preempt),
    ("fig06_hybrid_vs_fifo", F.fig06_hybrid_vs_fifo),
    ("fig11_core_tuning", F.fig11_core_tuning),
    ("fig12_14_hybrid_vs_cfs", F.fig12_14_hybrid_vs_cfs),
    ("fig15_17_time_limit", F.fig15_17_time_limit),
    ("fig18_19_rightsizing", F.fig18_19_rightsizing),
    ("fig20_table1_cost", F.fig20_table1_cost),
    ("fig21_22_microvm", F.fig21_22_microvm),
    ("fig23_pareto", F.fig23_pareto),
    ("serving_gateway", S.serving_gateway),
    ("cluster_matrix", C.cluster_matrix),
    ("resilience_matrix", R.resilience_matrix),
    ("topology_matrix", T.topology_matrix),
    ("llm_faas", L.llm_faas_matrix),
    ("costmodel", CM.costmodel_matrix),
]


def main() -> None:
    import json
    from .common import RESULTS
    args = [a for a in sys.argv[1:] if a != "--reuse"]
    only = args[0] if args else None
    reuse = "--reuse" in sys.argv
    print("name,us_per_call,derived", flush=True)
    for name, fn in BENCHES:
        if only and only not in name:
            continue
        path = RESULTS / f"{name}.json"
        if reuse and path.exists():
            rows = json.loads(path.read_text())
            emit(name, rows, 0.0)
            continue
        rows, dt = timed(fn)
        emit(name, rows, dt)


if __name__ == "__main__":
    main()
