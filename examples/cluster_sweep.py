"""Fleet simulation quickstart: does the paper's hybrid still win once a
cluster dispatcher sits in front of the nodes?

Runs a 5-dispatcher x {cfs, hybrid} x {2, 4}-node grid in parallel and
prints the cost matrix plus the serial-vs-parallel sweep speedup.

    python examples/cluster_sweep.py
"""
import repro
from repro import FleetSpec, PolicySpec, Scenario, WorkloadSpec
from repro.cluster import build_grid, compare_serial
from repro.traces import TraceSpec


def main():
    # -- one cell, spelled out ------------------------------------------------
    spec = TraceSpec(minutes=1, invocations_per_min=1200, n_functions=80,
                     seed=0)
    res = repro.run(Scenario(
        workload=WorkloadSpec(kind="azure", trace=spec),
        fleet=FleetSpec(n_nodes=4, cores_per_node=8,
                        dispatcher="join_idle_queue"),
        policy=PolicySpec(name="hybrid"))).raw
    s = res.summary()
    print(f"one cell: {s['n_nodes']} nodes x {s['cores_per_node']} cores, "
          f"{s['dispatcher']} dispatch, hybrid nodes")
    print(f"  cost ${s['cost_usd']:.4f}  "
          f"p99 slowdown {s['p99_slowdown']:.1f}x  "
          f"util {s['util_mean']:.2f} (range {s['util_range']:.2f})\n")

    # -- the full grid, in parallel -------------------------------------------
    grid = build_grid(
        ["cfs", "hybrid"],
        ["random", "round_robin", "least_loaded", "join_idle_queue",
         "affinity"],
        [2, 4], cores_per_node=8, minutes=1, invocations_per_min=1200.0,
        n_functions=80)
    cmp = compare_serial(grid)
    print(f"{len(grid)}-cell sweep: serial {cmp['serial_s']:.1f}s, "
          f"parallel {cmp['parallel_s']:.1f}s "
          f"({cmp['speedup']:.1f}x speedup)\n")

    print(f"{'node policy':<12} {'dispatcher':<16} {'nodes':>5} "
          f"{'cost $':>9} {'p99 slow':>9}")
    for row in sorted(cmp["rows"], key=lambda r: r["cost_usd"]):
        print(f"{row['node_policy']:<12} {row['dispatcher']:<16} "
              f"{row['n_nodes']:>5} {row['cost_usd']:>9.4f} "
              f"{row['p99_slowdown']:>9.1f}")


if __name__ == "__main__":
    # compare_serial starts a spawn-context process pool, whose workers
    # re-import this module.
    main()
