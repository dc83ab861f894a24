"""Batching layer: Scenario cells -> packed arrays -> one device program.

``run_scenarios`` takes a list of in-regime scenarios (see
``repro.mc.dispatch.supported``), decomposes each into kernel UNITS —
a single-node cell is one unit; an admitted flat fleet becomes one
unit per node, holding the dispatch subsequence its state-oblivious
dispatcher (round_robin/random) is replayed to in Python — groups
units into (n_cores, padded task count) shape buckets, advances each
bucket's whole grid in ONE vmapped XLA program, then rebuilds ordinary
``Task`` / ``SimResult`` / ``ClusterResult`` / ``ScenarioResult``
objects from the output arrays — so every downstream consumer (summary
schema, cost roll-ups, gate, dashboard) reads exactly what the scalar
engine would have produced, bit-for-bit (DESIGN.md Sec. 16).
"""
from __future__ import annotations

import itertools
import random
from typing import TYPE_CHECKING, Optional, Sequence

import numpy as np

from .dispatch import (enable_compile_cache, reason_key, supported,
                       tasks_supported)
from .spans import EVENT_LAYERS, FIRE_LAYERS, gc_spans, span

if TYPE_CHECKING:
    from ..scenario import Scenario, ScenarioResult

_INF = float("inf")

# Hybrid defaults mirrored from core.hybrid.HybridScheduler.
_HYBRID_TIME_LIMIT_MS = 1633.0

# Numbers the `run_scenarios` calls of this process; the `call` stat
# ties the spans of one call together.
_CALLS = itertools.count()


def _bucket(n: int) -> int:
    """Padded task-slot count: next power of two, floor 64 — few
    compilations, bounded padding waste."""
    return max(64, 1 << max(0, (n - 1)).bit_length())


def cell_params(sc: "Scenario") -> tuple[int, float]:
    """(n_fifo, fifo budget limit) for a supported scenario — the two
    traced per-cell scalars that select the policy inside the kernel."""
    C = sc.fleet.cores_per_node
    name = sc.policy.name
    if name == "fifo":
        return C, _INF
    if name == "cfs":
        return 0, _INF
    n_fifo = sc.policy.kw.get("n_fifo", C // 2)
    limit = float(sc.policy.kw.get("time_limit_ms",
                                   _HYBRID_TIME_LIMIT_MS))
    return n_fifo, limit


def replay_assignments(sc: "Scenario", n_tasks: int) -> list[int]:
    """Node index per task, in canonical stream order — exact because
    the admitted dispatchers never observe node state: ``round_robin``
    is a counter over dispatch order, ``random`` draws once per
    dispatch from ``random.Random(fleet.seed)``, and the gate's
    canonical-stream check (tids == indices, arrivals non-decreasing)
    makes ``ClusterSim``'s (arrival, tid) dispatch order the list
    order."""
    name = sc.fleet.dispatcher
    n = sc.fleet.n_nodes
    if name == "round_robin":
        return [k % n for k in range(n_tasks)]
    if name == "random":
        rng = random.Random(sc.fleet.seed)
        return [rng.randrange(n) for _ in range(n_tasks)]
    raise ValueError(f"dispatcher {name!r} is not replayable")


def _fleet_result(sc: "Scenario", tasks, sel: list[int]):
    """Rebuild the exact ``ClusterResult`` that ``ClusterSim.result()``
    produces for an in-regime fleet: flat node0..node{n-1} roster,
    assignments in dispatch order, unit price multipliers, no
    resilience bookkeeping (all those layers are gate-refused)."""
    from ..cluster.metrics import ClusterResult
    from ..core.metrics import SimResult

    fl, pol = sc.fleet, sc.policy
    n = fl.n_nodes
    node_ids = [f"node{i}" for i in range(n)]
    per_node: list[list] = [[] for _ in range(n)]
    for j, i in enumerate(sel):
        per_node[i].append(tasks[j])
    node_results = [
        SimResult(policy=pol.name, tasks=ts,
                  total_ctx=sum(t.ctx_switches for t in ts))
        for ts in per_node]
    meta = [{"node_id": nid, "zone": None, "rack": None, "sku": None,
             "spot": False, "price_mult": 1.0, "base_price_mult": 1.0,
             "spot_discount": 0.0} for nid in node_ids]
    return ClusterResult(
        node_results=node_results,
        node_ids=node_ids,
        node_policies=[pol.name] * n,
        dispatcher=fl.dispatcher,
        cores_per_node=fl.cores_per_node,
        assignments=[(tasks[j].tid, node_ids[i])
                     for j, i in enumerate(sel)],
        node_meta=meta,
    )


def route(scenarios: Sequence["Scenario"]
          ) -> tuple[list[Optional[str]], dict[int, tuple]]:
    """Partition cells between the engines before any work happens.

    Returns the per-cell refusal key (None for a batched cell, else a
    stable ``Refusal.key`` counter key) and, for every batched cell
    index, its built ``(tasks, meta)`` — handed on to
    ``run_scenarios(prebuilt=...)`` so each workload is built once.
    The dynamic half of the gate can still demote a caller-shaped
    stream here.
    """
    reasons: list[Optional[str]] = []
    built: dict[int, tuple] = {}
    for k, sc in enumerate(scenarios):
        why = supported(sc)
        if why is None:
            tasks, meta = sc.workload.build()
            why = tasks_supported(tasks)
            if why is None:
                built[k] = (tasks, meta)
        reasons.append(None if why is None else reason_key(why))
    return reasons, built


def run_scenarios(scenarios: Sequence["Scenario"],
                  prebuilt: Optional[Sequence] = None
                  ) -> list["ScenarioResult"]:
    """Run in-regime scenarios on the batched engine.

    ``prebuilt`` optionally supplies ``(tasks, meta)`` per scenario
    (e.g. ``MonteCarlo`` shares one trace generation across load
    scales); otherwise each ``workload.build()`` runs here. Raises
    ``ValueError`` on out-of-regime scenarios — callers partition
    with ``dispatch.supported`` first.  Each result carries
    ``mc_stats``, the kernel's counters (the algorithmic multi-event
    win stays visible even where 1-core wall-clock hides it):

    * ``iters``, ``events``: kernel while-loop trips and scheduling
      events retired, summed over fleet units;
    * ``events_by_layer``: those events split by the layer that retired
      them (a dict keyed by ``spans.EVENT_LAYERS``: ``cycle_ff``,
      ``window_ff``, ``advance``, ``micro``; its values add up to
      ``events``), summed over fleet units;
    * ``fires_by_layer``: the (trip, core) firings of the two
      multi-event layers (keyed by ``spans.FIRE_LAYERS``: ``cycle_ff``,
      ``window_ff``), summed over fleet units;
    * ``rq_depth_max``: the deepest CFS runqueue at the end of a trip,
      the largest over fleet units;
    * ``rotation_deep_trips``: the trips that began with a CFS runqueue
      deeper than the rotation's pop prefix (``kernels._CYCLE_K - 1``),
      summed over fleet units;
    * ``window_trips``: the trips on which the cell's batch ran the
      window fast-forward, which it runs wherever some core of some
      cell retires a chunk by it, summed over fleet units. Unlike the
      others this counter is the batch's: alone a cell counts the trips
      on which it fires itself, and in a batch at least those;
    * ``window_len``: W, the chunks a firing scans
      (``kernels.window_len``), the largest over the units' buckets.

    ``fires_by_layer``, ``rq_depth_max``, ``rotation_deep_trips`` and
    ``window_trips`` are left out where the grid function returns only
    the observables and the events (a stand-in for ``run_grid``).

    Under ``jax.profiler`` the call writes host spans (``spans.span``),
    each with the stat ``call``, this process's number of the call:

    * ``mc.pack`` (also ``C``, ``N``, ``B``): filling one program's
      ``[B, N]`` arrays;
    * ``mc.launch`` (``C``, ``N``, ``B``; ``commit``, the form of the
      window fast-forward's commit, ``kernels.window_commit``; and
      ``window``, its length W, ``kernels.window_len``): ``run_grid``,
      from the copy to the device through the program to
      ``device_get``;
    * ``mc.rebuild``: the ``Task`` writes of one program (``C``, ``N``,
      ``B``), and one more for building the results;
    * ``mc.gc`` (``generation``): every garbage collection inside the
      call.
    """
    enable_compile_cache()
    with gc_spans():
        return _run(scenarios, prebuilt, call=next(_CALLS))


def _run(scenarios, prebuilt, call: int) -> list["ScenarioResult"]:
    """``run_scenarios``'s work; ``call`` is the spans' ``call`` stat."""
    from ..core.metrics import SimResult
    from ..scenario import ScenarioResult
    from .kernels import run_grid, window_commit, window_len

    built = []
    for k, sc in enumerate(scenarios):
        why = supported(sc)
        tasks = meta = None
        if why is None:
            tasks, meta = (prebuilt[k] if prebuilt is not None
                           else sc.workload.build())
            why = tasks_supported(tasks)
        if why is not None:
            raise ValueError(f"scenario outside the batched regime "
                             f"({why}); route it to the scalar engine")
        built.append((tasks, meta))

    # Kernel units: (scenario idx, node idx | None, task index list).
    # Admitted fleets decompose node-by-node — the nodes never
    # interact once assignments are fixed, so each is an independent
    # cell batched alongside everything else.
    units: list[tuple[int, Optional[int], list[int]]] = []
    fleet_sel: dict[int, list[int]] = {}
    for k, sc in enumerate(scenarios):
        n = len(built[k][0])
        if sc.fleet.is_fleet:
            sel = replay_assignments(sc, n)
            fleet_sel[k] = sel
            for i in range(sc.fleet.n_nodes):
                idxs = [j for j in range(n) if sel[j] == i]
                if idxs:          # an empty node needs no kernel cell
                    units.append((k, i, idxs))
        else:
            units.append((k, None, list(range(n))))

    # Shape buckets: one compiled program per (C, N) pair.
    groups: dict[tuple[int, int], list[int]] = {}
    for u, (k, _i, idxs) in enumerate(units):
        key = (scenarios[k].fleet.cores_per_node, _bucket(len(idxs)))
        groups.setdefault(key, []).append(u)

    iters = [0] * len(scenarios)   # kernel while-loop trips per cell
    events = [0] * len(scenarios)  # scheduling events retired per cell
    by_layer = np.zeros((len(scenarios), len(EVENT_LAYERS)), np.int64)
    fires = np.zeros((len(scenarios), len(FIRE_LAYERS)), np.int64)
    depth = [0] * len(scenarios)   # deepest runqueue per cell
    deep = [0] * len(scenarios)    # trips that began deep, per cell
    wtrips = [0] * len(scenarios)  # trips the batch ran the window
    wlen = [0] * len(scenarios)    # window length per cell
    counted = True                 # the grid counts firings and depth
    for (C, N), us in groups.items():
        B = len(us)
        with span("pack", call=call, C=C, N=N, B=B):
            arrival = np.full((B, N), _INF)
            service = np.full((B, N), 1.0)
            n_tasks = np.zeros(B, np.int32)
            n_fifo = np.zeros(B, np.int32)
            limit = np.zeros(B)
            for b, u in enumerate(us):
                k, _i, idxs = units[u]
                tasks = built[k][0]
                arrival[b, :len(idxs)] = [tasks[j].arrival for j in idxs]
                service[b, :len(idxs)] = [tasks[j].service for j in idxs]
                n_tasks[b] = len(idxs)
                n_fifo[b], limit[b] = cell_params(scenarios[k])
        with span("launch", call=call, C=C, N=N, B=B,
                  commit=window_commit(N), window=window_len(N)):
            out = run_grid(arrival, service, n_tasks, n_fifo, limit,
                           n_cores=C)
        counted = counted and "fires_by_layer" in out
        if not bool(np.all(out["ok"])):
            bad = sorted({units[us[b]][0] for b in range(B)
                          if not out["ok"][b]})
            raise RuntimeError(
                f"batched MC kernel failed to drain cells {bad} "
                f"(iteration cap hit or tasks left unfinished) — "
                f"regime bug, please report")
        with span("rebuild", call=call, C=C, N=N, B=B):
            for b, u in enumerate(us):
                k, _i, idxs = units[u]
                tasks = built[k][0]
                for pos, j in enumerate(idxs):
                    task = tasks[j]
                    task.completion = float(out["completion"][b, pos])
                    task.first_run = float(out["first_run"][b, pos])
                    task.preemptions = int(out["preemptions"][b, pos])
                    task.ctx_switches = int(out["ctx_switches"][b, pos])
                    task.migrations = int(out["migrations"][b, pos])
                    task.cpu_time = float(out["cpu_time"][b, pos])
                    task.remaining = 0.0
                iters[k] += int(out["n_iters"][b])
                events[k] += int(out["n_events"][b])
                by_layer[k] += out["events_by_layer"][b]
                wlen[k] = max(wlen[k], window_len(N))
                if counted:
                    fires[k] += out["fires_by_layer"][b]
                    depth[k] = max(depth[k], int(out["rq_depth_max"][b]))
                    deep[k] += int(out["rotation_deep_trips"][b])
                    wtrips[k] += int(out["window_trips"][b])

    results: list["ScenarioResult"] = []
    with span("rebuild", call=call):
        for k, sc in enumerate(scenarios):
            tasks, meta = built[k]
            if sc.fleet.is_fleet:
                raw = _fleet_result(sc, tasks, fleet_sel[k])
            else:
                raw = SimResult(
                    policy=sc.policy.name, tasks=tasks,
                    total_ctx=sum(t.ctx_switches for t in tasks))
            stats = {"iters": iters[k], "events": events[k],
                     "events_by_layer": dict(zip(
                         EVENT_LAYERS, map(int, by_layer[k]))),
                     "window_len": wlen[k]}
            if counted:
                stats["fires_by_layer"] = dict(zip(
                    FIRE_LAYERS, map(int, fires[k])))
                stats["rq_depth_max"] = depth[k]
                stats["rotation_deep_trips"] = deep[k]
                stats["window_trips"] = wtrips[k]
            results.append(ScenarioResult(
                scenario=sc, raw=raw, meta=dict(meta), mc_stats=stats,
                backend="jax"))
    return results
