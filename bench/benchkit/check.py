"""The comparison that decides ``correct``.

Every cell the window's sweeps produced is held to the plain reference
(``refsched``) run on the benchmark's own traffic. Four numbers are
compared, each against a limit stated in the configuration file:

* ``traffic_diff`` -- invocations the program built that differ from
  the benchmark's traffic (arrival, service or memory size), or are
  missing or extra. Exact: limit 0.
* ``int_diff`` -- integer observables that differ: per task the
  preemptions, context switches and migrations the kernel counted and
  the rebuild wrote into ``Task``; per summary row the finished count,
  preemptions and context switches. Exact: limit 0.
* ``time_dev`` -- the largest gap of a per-task time (completion,
  first run, CPU time) from the reference, as a share of the cell's
  makespan: the kernel's arithmetic and the rebuild into ``Task``.
* ``row_dev`` -- the largest gap of a float field of a summary row: a
  time (percentiles, makespan, mean execution) as a share of the
  makespan, the bill relative to itself. The roll-up.

A cell that never produced an answer makes ``failed_cells`` count.
"""
from __future__ import annotations

import math

import numpy as np

from . import refsched, traffic

INT_FIELDS = ("preemptions", "ctx_switches", "migrations")
TIME_FIELDS = ("completion", "first_run", "cpu_time")
ROW_INT = ("n", "preemptions", "ctx_switches")
ROW_COST = ("cost_usd", "usd_per_1k_requests")
ROW_FLOAT = ("mean_execution_s", "p50_execution_s", "p99_execution_s",
             "p99_response_s", "p99_turnaround_s", "makespan_s") + ROW_COST
NUMBERS = ("traffic_diff", "int_diff", "time_dev", "row_dev",
           "failed_cells")


def observed(result) -> dict:
    """A program result's per-task observables and summary row."""
    tasks = sorted(result.raw.tasks, key=lambda t: t.tid)
    out = {f: np.array([getattr(t, f) for t in tasks], np.float64)
           for f in ("tid", "arrival", "service", "mem_mb") + TIME_FIELDS}
    for f in INT_FIELDS:
        out[f] = np.array([getattr(t, f) for t in tasks], np.int64)
    out["row"] = result.summary()
    return out


def reference(config: dict, plan_, cell, dtype=float) -> dict:
    """The plain reference's answer for one cell, in the layout of
    ``observed``."""
    tr = traffic.cell_traffic(plan_, cell)
    out = refsched.simulate(
        tr["arrival"], tr["service"], config["cores_per_node"],
        cell.policy, n_fifo=cell.kw.get("n_fifo"),
        limit_ms=cell.kw.get("time_limit_ms", refsched.HYBRID_LIMIT_MS),
        dtype=dtype)
    out["tid"] = np.arange(len(tr["arrival"]), dtype=np.float64)
    out["arrival"], out["service"] = tr["arrival"], tr["service"]
    out["mem_mb"] = tr["mem_mb"].astype(np.float64)
    out["row"] = refsched.rollup(tr["arrival"], tr["mem_mb"], out)
    return out


def _gap(a, b) -> float:
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    d = np.where(np.isnan(d), np.inf, d)
    return float(d.max()) if d.size else 0.0


def compare(got: dict, ref: dict) -> dict:
    """Readings of one cell's answer against the reference's."""
    n = len(ref["tid"])
    if len(got["tid"]) != n:
        return {"traffic_diff": max(n, len(got["tid"])),
                "int_diff": max(n, len(got["tid"])),
                "time_dev": math.inf, "row_dev": math.inf}
    traffic_diff = int(np.sum(
        (got["tid"] != ref["tid"]) | (got["arrival"] != ref["arrival"])
        | (got["service"] != ref["service"])
        | (got["mem_mb"] != ref["mem_mb"])))
    int_diff = sum(int(np.sum(got[f] != ref[f])) for f in INT_FIELDS)
    int_diff += sum(got["row"][f] != ref["row"][f] for f in ROW_INT)
    span = float(ref["completion"].max())
    time_dev = max(_gap(got[f], ref[f]) for f in TIME_FIELDS) / span
    # Times in a row as a share of the makespan, as per task; the bill
    # (never 0: every request pays its fee) relative to itself.
    scale = {f: abs(ref["row"][f]) if f in ROW_COST
             else ref["row"]["makespan_s"] for f in ROW_FLOAT}
    row_dev = max(_gap(got["row"][f], ref["row"][f]) / scale[f]
                  for f in ROW_FLOAT)
    return {"traffic_diff": traffic_diff, "int_diff": int(int_diff),
            "time_dev": time_dev, "row_dev": row_dev}


def worst(readings: list, failed_cells: int) -> dict:
    """The run's reading of each number: the worst over its cells."""
    out = {k: 0 for k in NUMBERS}
    for r in readings:
        for k, v in r.items():
            out[k] = max(out[k], v)
    out["failed_cells"] = failed_cells
    return out


def limits(config: dict) -> dict:
    return dict(config["correct"], failed_cells=0)


def judge(readings: dict, lim: dict) -> bool:
    return all(readings[k] <= lim[k] for k in NUMBERS)
