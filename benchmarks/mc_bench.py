"""Monte-Carlo sweep throughput: cells/sec, batched JAX vs mp.Pool.

The batched MC engine (``repro.mc``, DESIGN.md Sec. 16) advances a
whole (seeds x loads x policies) grid of in-regime sweep cells in one
vmapped XLA program, bit-identical to the scalar engine. Since ISSUE 9
the kernel retires MANY scheduling events per ``lax.while_loop``
iteration (alternation-cycle + window closed forms + micro-step
chain), so this bench reports two axes:

* WALL — cells/sec for the POOL baseline (``cluster.sweep.run_sweep``
  over the multiprocessing pool at full worker count, each worker
  running the scalar engine) vs the JAX backend timed COLD (first
  call: XLA compilation included) and WARM (compiled program cached).
* ALGORITHM — kernel iterations and events retired per cell from the
  kernel's own counters. ``events_per_cell / iters_per_cell`` is the
  multi-event win, and because the PR 7 one-event kernel ran at
  exactly one event per iteration, ``events_per_cell`` IS its
  iteration count: ``iter_reduction_vs_one_event`` is directly the
  "x fewer iterations" acceptance number, visible even on 1-core CI
  where wall-clock hides it.

READ THE WALL HEADLINE WITH THE MACHINE IN MIND: one compiled program
does O(padded-slots) vector work per iteration across the whole
batch and the vmapped while-loop runs to the batch's SLOWEST cell,
where the scalar engine does O(1) dict work per event and
fast-forwards dense regimes analytically. On parallel hardware
(many-core CPU, GPU/TPU) the batch axis is free and the one-program
shape wins; on a single-core CI runner XLA executes the batch
serially and the pool baseline stays ahead. ``meta`` records
``cpu_count``, ``pool_workers``, the compile time, and the persistent
compile-cache hit evidence (entry counts of the cache directory:
``JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache/``), so a
number measured on one
machine is never mistaken for a hardware-independent ratio; CI gates
cells/sec run-over-run on same-``cpu_count`` runners (kind ``mc`` in
``benchmarks.regression_gate``) rather than against an absolute
cross-machine target.

Equivalence is re-asserted on a sample of cells each run (summaries
must match the pool rows exactly) — a throughput number for a wrong
simulation would be worse than no number.

Standalone::

    python -m benchmarks.mc_bench [--smoke] [--median-of N]

``--median-of N`` repeats each timed measurement N times and keeps
the median (matching engine_bench's smoke aggregation) — sub-second
smoke grids otherwise gate on single-run scheduler noise.

Writes ``results/benchmarks/BENCH_mc.json``:

    {"rows": [{"policy": ..., "backend": "pool" | "jax" | "jax_cold",
               "n_cells": ..., "n_cores": ..., "n_tasks": ...,
               "wall_s": ..., "cells_per_sec": ...}, ...],
     "meta": {"headline_speedup_vs_pool": ..., "compile_s": ...,
              "cpu_count": ..., "padded_slots": ...,
              "iters_per_cell": ..., "events_per_cell": ...,
              "iter_reduction_vs_one_event": ..., ...}}
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time
from dataclasses import asdict

from repro.cluster.sweep import build_grid, run_sweep

from .common import RESULTS

ARTIFACT = "BENCH_mc.json"

POLICIES = ("fifo", "cfs", "hybrid")

# Full tier: 16 seeds x 6 loads x 3 policies = 288 cells (the >= 256
# acceptance floor), each one minute of a small Azure-like trace on a
# 4-core node — the many-small-cells shape Monte-Carlo sweeps take.
FULL = dict(seeds=range(16), loads=(0.25, 0.5, 1.0, 1.5, 2.0, 3.0),
            minutes=1, invocations_per_min=60.0, n_functions=10,
            n_cores=4)
# Smoke tier (CI): same shape, 12 cells, finishes in well under a
# minute including the one XLA compile.
SMOKE = dict(seeds=range(2), loads=(0.5, 1.5),
             minutes=1, invocations_per_min=60.0, n_functions=10,
             n_cores=4)

# How many cells of each timed grid get their pool/jax summary rows
# byte-compared (bit-identity spot check riding along with the bench).
VERIFY_CELLS = 6


def _cpu_count() -> int:
    """Cores this process may actually use. ``os.cpu_count()`` ignores
    affinity masks, so under CI's ``taskset -c 0,1`` pinning it would
    report the whole runner and the gate key would lie about the
    machine the walls were measured on."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        return os.cpu_count() or 2


def mc_grid(spec: dict) -> list:
    return build_grid(
        POLICIES, ["none"], [1], tuple(spec["loads"]),
        cores_per_node=spec["n_cores"], minutes=spec["minutes"],
        invocations_per_min=spec["invocations_per_min"],
        n_functions=spec["n_functions"])


def _expand_seeds(grid: list, seeds) -> list:
    from dataclasses import replace
    return [replace(c, seed=s) for c in grid for s in seeds]


def _strip(row: dict) -> dict:
    return {k: v for k, v in row.items() if k != "backend"}


def _jax_sweep(grid: list) -> tuple[list[dict], int, int]:
    """The sweep runner's jax route, inlined so the TIMED run also
    yields the kernel's iteration/event counters (``run_sweep`` rows
    drop ``mc_stats``). Returns (rows, total_iters, total_events)."""
    from repro.mc.engine import route, run_scenarios

    scs = [c.to_scenario() for c in grid]
    reasons, built = route(scs)
    refused = {why for why in reasons if why is not None}
    if refused:
        raise RuntimeError(
            f"bench cells outside the batched regime ({sorted(refused)}) "
            "— the bench grid must ride the device end to end")
    results = run_scenarios(scs, prebuilt=[built[k] for k in range(len(scs))])
    rows, iters, events = [], 0, 0
    for cell, res in zip(grid, results):
        rows.append({**asdict(cell), **res.summary()})
        iters += res.mc_stats["iters"]
        events += res.mc_stats["events"]
    return rows, iters, events


def bench_grid(spec: dict, median_of: int = 1) -> tuple[list[dict], dict]:
    from repro.mc.dispatch import compile_cache_entries, enable_compile_cache
    from repro.mc.engine import _bucket

    grid = _expand_seeds(mc_grid(spec), spec["seeds"])
    n_cells = len(grid)
    pool_workers = min(n_cells, _cpu_count())

    pool_walls = []
    for _ in range(median_of):
        t0 = time.perf_counter()
        pool_rows = run_sweep(grid, parallel=True,
                              processes=pool_workers)
        pool_walls.append(time.perf_counter() - t0)
    pool_s = statistics.median(pool_walls)

    cache_dir = enable_compile_cache()
    cache_before = compile_cache_entries()
    t0 = time.perf_counter()
    jax_rows, _, _ = _jax_sweep(grid)
    cold_s = time.perf_counter() - t0
    cache_after_cold = compile_cache_entries()

    warm_walls = []
    for _ in range(median_of):
        t0 = time.perf_counter()
        jax_rows, iters, events = _jax_sweep(grid)
        warm_walls.append(time.perf_counter() - t0)
    warm_s = statistics.median(warm_walls)

    step = max(1, n_cells // VERIFY_CELLS)
    for k in range(0, n_cells, step):
        if _strip(jax_rows[k]) != _strip(pool_rows[k]):
            raise RuntimeError(
                f"bit-identity violated on bench cell {k}: "
                f"{pool_rows[k]} != {jax_rows[k]}")

    n_tasks = pool_rows[0]["n"]
    # Per-policy walls are not separable inside one batched program;
    # the artifact's gated rows are the all-policies aggregates per
    # backend (plus the cold row, reported but gate-exempt: its wall
    # is dominated by the one-off XLA compile). cpu_count rides on
    # every row because the gate keys on it: both backends' walls
    # scale with core count (pool workers / XLA intra-op threads), so
    # differently-sized runners must never cross-compare.
    cpus = _cpu_count()
    rows = [
        {"policy": "all", "backend": "pool", "n_cells": n_cells,
         "n_cores": spec["n_cores"], "n_tasks": n_tasks,
         "cpu_count": cpus,
         "wall_s": pool_s, "cells_per_sec": n_cells / pool_s},
        {"policy": "all", "backend": "jax", "n_cells": n_cells,
         "n_cores": spec["n_cores"], "n_tasks": n_tasks,
         "cpu_count": cpus,
         "wall_s": warm_s, "cells_per_sec": n_cells / warm_s},
        {"policy": "all", "backend": "jax_cold", "n_cells": n_cells,
         "n_cores": spec["n_cores"], "n_tasks": n_tasks,
         "cpu_count": cpus,
         "wall_s": cold_s, "cells_per_sec": n_cells / cold_s},
    ]
    meta = {
        "n_cells": n_cells,
        "n_tasks_per_cell": n_tasks,
        "padded_slots": _bucket(n_tasks),
        "grid": {k: (list(v) if isinstance(v, (range, tuple)) else v)
                 for k, v in spec.items()},
        "median_of": median_of,
        "pool_s": pool_s,
        "pool_workers": pool_workers,
        "jax_cold_s": cold_s,
        "jax_warm_s": warm_s,
        "compile_s": cold_s - warm_s,
        "headline_speedup_vs_pool": pool_s / warm_s,
        # Kernel-side counters: events_per_cell is exactly what the
        # PR 7 one-event kernel spent in iterations, so the reduction
        # ratio is the hardware-independent multi-event win.
        "iters_per_cell": iters / n_cells,
        "events_per_cell": events / n_cells,
        "events_per_iter": events / max(iters, 1),
        "iter_reduction_vs_one_event": events / max(iters, 1),
        "compile_cache": {
            "dir": cache_dir, "entries_before": cache_before,
            "entries_after_cold": cache_after_cold,
            # cold run hit the cache iff no new entries appeared
            "cold_was_hit": cache_after_cold == cache_before},
        "cpu_count": cpus,
        "verified_cells": len(range(0, n_cells, step)),
    }
    return rows, meta


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    median_of = 1
    if "--median-of" in argv:
        median_of = int(argv[argv.index("--median-of") + 1])
    rows, meta = bench_grid(SMOKE if smoke else FULL,
                            median_of=median_of)
    meta["smoke"] = smoke
    payload = {"rows": rows, "meta": meta}
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / ARTIFACT).write_text(json.dumps(payload, indent=2))
    print("policy,backend,n_cells,n_cores,wall_s,cells_per_sec")
    for r in rows:
        print(f"{r['policy']},{r['backend']},{r['n_cells']},"
              f"{r['n_cores']},{r['wall_s']:.3f},"
              f"{r['cells_per_sec']:.1f}")
    print(f"# headline: jax-warm vs pool "
          f"{meta['headline_speedup_vs_pool']:.2f}x on "
          f"{meta['n_cells']} cells "
          f"(compile {meta['compile_s']:.1f}s, "
          f"cpu_count={meta['cpu_count']})", file=sys.stderr)
    print(f"# kernel: {meta['iters_per_cell']:.1f} iters/cell for "
          f"{meta['events_per_cell']:.1f} events/cell = "
          f"{meta['iter_reduction_vs_one_event']:.1f}x fewer "
          f"iterations than the one-event kernel", file=sys.stderr)


if __name__ == "__main__":
    main()
