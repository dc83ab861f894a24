"""One run of one cell: set-up, the measured window, the check.

A run is one client sending the cell's sweep back to back, closed loop:
set-up builds the plan from ``--seed`` and runs one warm-up sweep, which
compiles (or loads from the persistent cache) exactly the cell's own
programs; the window then repeats the same sweep until ``seconds`` have
passed, finishing the sweep in flight, and is timed from the start of
its first sweep to the end of its last. Each sweep goes through the
program's own front door:

1. ``bench.build``: the workloads from the program's trace generator,
   one ``Scenario`` per cell, and ``repro.mc.engine.route``;
2. ``bench.engine``: ``run_scenarios(prebuilt=...)``, the batched
   device programs and the rebuild of ``Task``/``SimResult``;
3. ``bench.rollup``: ``ScenarioResult.summary()`` for every cell.

Once the window has closed, every answer it produced is held to the
plain reference (``check``).
"""
from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from typing import Optional

from . import check, spec, tracefile, traffic

CACHE_DIR = os.path.join(spec.ROOT, ".bench_cache", "jax")


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Sweep:
    n_cells: int
    failed: int = 0
    device_cells: int = 0
    tasks: int = 0
    # (B, max n_iters, sum n_iters) per device program
    programs: list = field(default_factory=list)
    results: list = field(default_factory=list)
    host_s: dict = field(default_factory=dict)


@dataclass
class RunView:
    """What a per-layer metric's reader reads."""
    sweeps: list
    trace: Optional[dict]
    compile: dict


class CompileMonitor:
    """Backend compiles (``compile_or_get_cached`` calls, cache loads
    included) and their seconds, per phase of the run, from JAX's own
    monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.phase = "setup"
        self.seconds = {}
        self.events = {}
        self.cache = {}
        monitoring.register_event_duration_secs_listener(self._on)

        monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            p = self.phase
            self.seconds[p] = self.seconds.get(p, 0.0) + duration
            self.events[p] = self.events.get(p, 0) + 1

    def _on_event(self, event, **_):
        if event.startswith("/jax/compilation_cache/"):
            key = (self.phase, event.rsplit("/", 1)[1])
            self.cache[key] = self.cache.get(key, 0) + 1


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_sweep(config: dict, plan_) -> Sweep:
    import jax
    from repro.mc.engine import _bucket, route, run_scenarios

    sw = Sweep(n_cells=len(plan_.cells))
    sw.results = [None] * sw.n_cells
    with jax.profiler.TraceAnnotation(tracefile.SWEEP):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.build"):
            scenarios = traffic.build_scenarios(config, plan_)
            reasons, built = route(scenarios)
        t1 = time.perf_counter()
        dev = sorted(built)
        try:
            with jax.profiler.TraceAnnotation("bench.engine"):
                out = run_scenarios([scenarios[k] for k in dev],
                                    prebuilt=[built[k] for k in dev])
        except Exception:   # a sweep that raised: its cells failed
            _log(traceback.format_exc())
            out = []
        t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.rollup"):
            rows = [r.summary() for r in out]
        t3 = time.perf_counter()
    sw.host_s = {"build": t1 - t0, "engine": t2 - t1, "rollup": t3 - t2}

    groups = {}
    for k, r, row in zip(dev, out, rows):
        if r.backend != "jax" or row["backend"] != "jax":
            continue
        sw.results[k] = r
        sw.device_cells += 1
        sw.tasks += row["n"]
        key = (scenarios[k].fleet.cores_per_node,
               _bucket(len(built[k][0])))
        groups.setdefault(key, []).append(r.mc_stats["iters"])
    sw.failed = sw.n_cells - sw.device_cells
    sw.programs = [(len(it), max(it), sum(it)) for it in groups.values()]
    return sw


def _device(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoDevice(f"need {chips} TPU chip(s); JAX found "
                       f"{len(devs)} {devs[0].platform} device(s) "
                       f"({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def _finite(x):
    return x if math.isfinite(x) else sys.float_info.max


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: str = spec.ROOT,
             cache_dir: str = CACHE_DIR, require_tpu: bool = True) -> dict:
    """One run; returns the result line's object.

    ``root`` is the checkout whose ``BENCHMARK.json`` names the cell,
    ``cache_dir`` JAX's persistent cache, and ``require_tpu=False`` lets
    a test drive the rest of a run on the CPU.
    """
    cell = spec.load_cell(cell_name, root)
    device = _device(cell.chips, require_tpu)

    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    monitor = CompileMonitor()

    plan_ = traffic.plan(cell.config, cell.traffic, seed)
    warm = run_sweep(cell.config, plan_)
    setup_s = time.time() - t_start
    _log(f"setup: {setup_s} s, warm-up sweep {warm.host_s}, "
         f"compile {monitor.seconds.get('setup', 0.0)} s in "
         f"{monitor.events.get('setup', 0)} events, cache {monitor.cache}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    monitor.phase = "window"
    if trace:
        # Device operations and the harness's own spans; no tracing of
        # every Python call, which would slow the host several times.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    sweeps = []
    w0 = time.perf_counter()
    while True:
        sweeps.append(run_sweep(cell.config, plan_))
        if time.perf_counter() - w0 >= seconds:
            break
    window_s = time.perf_counter() - w0
    if trace:
        jax.profiler.stop_trace()
    monitor.phase = "after"
    memory_peak = _memory_peak()
    for i, sw in enumerate(sweeps):
        _log(f"sweep {i}: {sw.host_s} programs {sw.programs} "
             f"failed {sw.failed}")

    # The check: every answer of the window against the reference.
    t_ref = time.perf_counter()
    readings = []
    for k, c in enumerate(plan_.cells):
        ref = check.reference(cell.config, plan_, c)
        for sw in sweeps:
            if sw.results[k] is not None:
                readings.append(check.compare(
                    check.observed(sw.results[k]), ref))
    attempted = sum(sw.n_cells for sw in sweeps)
    failed = sum(sw.failed for sw in sweeps)
    got = check.worst(readings, failed)
    lim = check.limits(cell.config)
    correct = check.judge(got, lim)
    _log(f"reference: {time.perf_counter() - t_ref} s for "
         f"{len(plan_.cells)} cells")

    tasks = sum(sw.tasks for sw in sweeps)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": {},
              "device": dict(device, memory_peak_bytes=memory_peak)}
    if trace:
        tr = tracefile.from_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        view = RunView(sweeps=sweeps, trace=tr, compile={
            "setup_s": monitor.seconds.get("setup", 0.0),
            "window_events": monitor.events.get("window", 0)})
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(view)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        bs = tracefile.busy_share(tr)
        if bs is not None:
            n_dev = max(tr["devices"], 1)
            result["device"]["busy_s"] = bs[0] / n_dev / 1e9
            result["device"]["window_s"] = bs[1] / 1e9
        result["breakdown"] = {"device_ops": tracefile.top_ops(tr),
                               "idle_gaps": tracefile.idle_gaps(tr)}
    else:
        e2e = {"sim_tasks_per_s": tasks / window_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    _log(f"window: {len(sweeps)} sweeps, {tasks} tasks in {window_s} s")
    result["checks"] = {k: {"value": _finite(got[k]), "limit": lim[k]}
                        for k in check.NUMBERS}
    for k in check.NUMBERS:
        _log(f"check {k} = {got[k]} (limit {lim[k]})")
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.time() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                          t_start=t_start)
    except NoDevice as e:
        _log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
