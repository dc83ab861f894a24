"""Chip smoke test: the batched Monte-Carlo engine on one TPU.

Runs the deployment the paper studies — one host with 50 cores under
the Azure-shaped trace (``TraceSpec()`` rates: 250 functions, 6,221
invocations a minute) — with the policies fifo, cfs and the static
hybrid (``n_fifo=25``, ``time_limit_ms=1633``), through the normal
front door ``MonteCarlo(..., backend="jax")``. The full grid (two
seeds of the 2-minute trace, 12,643 invocations at seed 0) does not
fit the script's 1200 s budget on one v5e chip, so it is cut to one
seed of the trace's first minute; the cuts are printed. It then

* checks that every cell ran on the device (no fallback),
* runs the scalar reference (``backend="python"``) on the same cells
  and holds the device to the equivalence tier of DESIGN.md Sec. 16:
  per-task integer observables exact, float observables and the
  summary's ``cost_usd`` / ``p99_turnaround_s`` within the limits
  derived from ``repro.mc.kernels.float_rtol`` (``compare_cell``),
* prints the cold wall time (compile included), the warm wall time,
  the compile seconds, whether JAX's persistent compilation cache was
  hit, and each cell's kernel trips and retired events.

The last line of standard output is one JSON object naming the device;
it is printed only when every check passed. Without a TPU the script
exits non-zero before any work, and never falls back to another
device. One process, one chip::

    python chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

# Cuts of the paper grid, forced by the 1200 s budget for the whole
# script, compile included: one pass over the full grid took 343 s
# (fifo, compile included) + 437 s (cfs) + hybrid on a v5e chip, and
# the script needs a cold and a warm pass. Seeds go first, then trace
# minutes.
SEEDS = (0,)
MINUTES = 1
CUTS = ("seeds (0, 1) -> (0,)",
        "trace minutes 2 -> 1 (TraceSpec rates unchanged)")
N_CORES = 50
POLICIES = {"fifo": {}, "cfs": {},
            "hybrid": {"n_fifo": 25, "time_limit_ms": 1633.0}}
INT_FIELDS = ("preemptions", "ctx_switches", "migrations")
FLOAT_FIELDS = ("completion", "first_run", "cpu_time")


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def compile_monitor() -> dict:
    """Count backend compile seconds and persistent-cache hits from
    JAX's own monitoring events."""
    from jax import monitoring

    seen = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compile_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["cache_misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return seen


def scenario(policy: str):
    from repro import FleetSpec, PolicySpec, Scenario, WorkloadSpec
    from repro.traces import TraceSpec
    return Scenario(workload=WorkloadSpec(kind="azure",
                                          trace=TraceSpec(minutes=MINUTES)),
                    fleet=FleetSpec(cores_per_node=N_CORES),
                    policy=PolicySpec(name=policy, kw=POLICIES[policy]))


def run_grid(backend: str) -> dict:
    from repro import MonteCarlo
    out = {}
    for p in POLICIES:
        t0 = time.perf_counter()
        out[p] = MonteCarlo(scenario(p), seeds=SEEDS, backend=backend).run()
        print(f"  {backend} {p}: {time.perf_counter() - t0} s", flush=True)
    return out


def compare_cell(dev, ref) -> tuple[dict, dict]:
    """Per-cell deviation of the device result from the reference, and
    the limit of each float deviation (DESIGN.md Sec. 16).

    Every reported time ends a chain of at most ``n_events`` double-f32
    operations on times no later than the horizon (the reference's last
    completion), so it lies within ``atol = float_rtol(n_events) *
    horizon`` ms of the reference. A turnaround or an execution is a
    difference of two such times (``2 atol``), and so is the p99 of
    turnarounds; ``cost_usd`` is linear in the executions, so its
    relative deviation is at most ``2 atol`` over the shortest one.
    """
    from repro.mc.kernels import float_rtol

    dt = sorted(dev.raw.tasks, key=lambda t: t.tid)
    rt = sorted(ref.raw.tasks, key=lambda t: t.tid)
    if [t.tid for t in dt] != [t.tid for t in rt]:
        fail("device and reference ran different task sets")
    out = {f: sum(getattr(a, f) != getattr(b, f) for a, b in zip(dt, rt))
           for f in INT_FIELDS}
    for f in FLOAT_FIELDS:
        out[f] = max(abs(getattr(a, f) - getattr(b, f))
                     for a, b in zip(dt, rt))
    sd, sr = dev.summary(), ref.summary()
    out["p99_turnaround_ms"] = abs(sd["p99_turnaround_s"] -
                                   sr["p99_turnaround_s"]) * 1e3
    out["cost_usd_rel"] = abs(sd["cost_usd"] - sr["cost_usd"]) / \
        sr["cost_usd"]
    out["n_diff"] = sd["n"] - sr["n"]

    atol = float_rtol(dev.mc_stats["events"]) * \
        max(t.completion for t in rt)
    limits = {f: atol for f in FLOAT_FIELDS}
    limits["p99_turnaround_ms"] = 2 * atol
    limits["cost_usd_rel"] = 2 * atol / min(t.execution for t in rt)
    return out, limits


def main() -> None:
    import jax

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        fail(f"no TPU: JAX found {dev0.platform!r} ({dev0.device_kind})")
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)
    for cut in CUTS:
        print(f"cut: {cut}", flush=True)

    # Run from a checkout: the package lives in its src/.
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.mc.dispatch import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    seen = compile_monitor()

    t0 = time.perf_counter()
    cold = run_grid("jax")
    cold_s = time.perf_counter() - t0
    compile_s, hits = seen["compile_s"], seen["cache_hits"]
    t0 = time.perf_counter()
    warm = run_grid("jax")
    warm_s = time.perf_counter() - t0
    print(f"cold_wall_s={cold_s} warm_wall_s={warm_s} "
          f"compile_s={compile_s} cache_hits={hits} "
          f"cache_misses={seen['cache_misses']} "
          f"cache_hit={hits > 0 and seen['cache_misses'] == 0}", flush=True)

    t0 = time.perf_counter()
    ref = run_grid("python")
    print(f"reference_wall_s={time.perf_counter() - t0}", flush=True)

    failures = []
    for pol in POLICIES:
        mc = warm[pol]
        if mc.meta["fallback"] != 0 or set(mc.meta["backends"]) != {"jax"}:
            failures.append(f"{pol}: cells fell back: {mc.meta}")
        for seed, d, c, r in zip(SEEDS, mc.results, cold[pol].results,
                                 ref[pol].results):
            if d.summary() != c.summary():
                failures.append(f"{pol} seed {seed}: warm != cold")
            dev_row = d.summary()
            if dev_row["backend"] != "jax" or \
                    dev_row["fallback_reason"] != "none":
                failures.append(f"{pol} seed {seed}: row says "
                                f"{dev_row['backend']}")
            dev, limits = compare_cell(d, r)
            print(f"cell policy={pol} seed={seed} n={dev_row['n']} "
                  f"n_iters={d.mc_stats['iters']} "
                  f"n_events={d.mc_stats['events']} "
                  + " ".join(f"{k}={v}" for k, v in dev.items()) + " "
                  + " ".join(f"{k}_limit={v}" for k, v in limits.items()),
                  flush=True)
            if dev["n_diff"] or any(dev[f] for f in INT_FIELDS):
                failures.append(f"{pol} seed {seed}: integer observables "
                                f"differ from the reference")
            bad = [f for f in limits if not dev[f] <= limits[f]]
            if bad:
                failures.append(f"{pol} seed {seed}: {bad} beyond their "
                                f"limits")
    if failures:
        fail("; ".join(failures))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
