"""The benchmark's own copy of the Azure-shaped trace generator.

This is the yardstick's traffic: the invocations every sweep must
simulate, made from a trace seed. It follows the paper's workload
construction (arXiv:2411.08448 Sec. V-B): per-function per-minute
invocation counts with lognormal bursts over a Zipf popularity, regular
spacing inside each minute, durations on a Fibonacci ladder with
per-invocation lognormal jitter, and the p90 duration pinned to
1,633 ms. Shapes follow Shahrad et al., USENIX ATC '20.

It repeats, operation for operation, the generator the program ships
(``repro.traces``), so the program's own build of a sweep can be held
to it exactly, and it imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

PHI = (1.0 + 5.0 ** 0.5) / 2.0
BUCKET_MS = tuple(80.0 * PHI ** i for i in range(16))
BUCKET_WEIGHTS = (0.17, 0.16, 0.15, 0.14, 0.13, 0.10, 0.075,
                  0.030, 0.016, 0.007, 0.005, 0.005, 0.005,
                  0.004, 0.002, 0.001)
MEMORY_MB = (128, 192, 256, 384, 512, 1024, 2048, 4096)
MEMORY_P = (0.45, 0.15, 0.15, 0.15, 0.05, 0.03, 0.015, 0.005)
P90_ANCHOR_MS = 1633.0

# The trace knobs a configuration may set, with the defaults of the
# paper's host (250 functions, 6,221 invocations a minute, two minutes).
TRACE_DEFAULTS = {"minutes": 2, "n_functions": 250,
                  "invocations_per_min": 6221.0, "burst_sigma": 0.55,
                  "duration_jitter": 0.08, "zipf_s": 1.1}


def _assign_buckets(pop: np.ndarray) -> np.ndarray:
    total = pop.sum()
    target = np.asarray(BUCKET_WEIGHTS) * total
    assigned = np.zeros(len(target))
    out = np.zeros(len(pop), dtype=np.int64)
    for i in np.argsort(-pop):
        b = int(np.argmax(target - assigned))
        out[i] = b
        assigned[b] += pop[i]
    return out


def trace(seed: int, minutes: int, n_functions: int,
          invocations_per_min: float, burst_sigma: float,
          duration_jitter: float, zipf_s: float) -> dict:
    """One trace as arrays in arrival order: ``arrival`` and
    ``service`` (ms, float64) and ``mem_mb`` (int)."""
    rng = np.random.default_rng(seed)
    n = n_functions
    mems = rng.choice(MEMORY_MB, size=n, p=MEMORY_P)
    pop = np.arange(1, n + 1, dtype=np.float64) ** (-zipf_s)
    rng.shuffle(pop)
    pop *= invocations_per_min / pop.sum()
    buckets = _assign_buckets(pop)
    target = invocations_per_min * minutes
    lam = np.empty((n, minutes))
    for i in range(n):
        burst = rng.lognormal(mean=-0.5 * burst_sigma ** 2,
                              sigma=burst_sigma, size=minutes)
        lam[i] = np.maximum(pop[i] * burst, 0.0)
    counts = rng.poisson(lam)
    realized = counts.sum()
    if realized > 0 and abs(realized - target) / target > 0.02:
        counts = rng.poisson(lam * (target / realized))

    events = []
    for f in range(n):
        for minute in range(minutes):
            k = int(counts[f, minute])
            if k <= 0:
                continue
            interval = 60_000.0 / k
            for j in range(k):
                events.append((minute * 60_000.0 + j * interval, f))
    events.sort()

    jrng = np.random.default_rng(seed + 1)
    services = np.empty(len(events))
    for i, (_, f) in enumerate(events):
        jitter = jrng.lognormal(mean=-0.5 * duration_jitter ** 2,
                                sigma=duration_jitter)
        services[i] = BUCKET_MS[buckets[f]] * jitter
    services *= P90_ANCHOR_MS / float(np.percentile(services, 90))
    return {"arrival": np.array([a for a, _ in events], np.float64),
            "service": services,
            "mem_mb": np.array([int(mems[f]) for _, f in events],
                               np.int64)}


def cut(tr: dict, horizon_s) -> dict:
    """The invocations that arrive in the first ``horizon_s`` seconds
    (all of them for ``None``)."""
    if horizon_s is None:
        return tr
    keep = tr["arrival"] < horizon_s * 1000.0
    return {k: v[keep] for k, v in tr.items()}


def scale_load(tr: dict, load: float) -> dict:
    """Compress inter-arrival times by ``load`` (> 1 is heavier)."""
    return dict(tr, arrival=tr["arrival"] / load) if load != 1.0 else tr
