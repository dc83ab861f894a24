"""Reduction from a profiler trace to the numbers the benchmark reports.

``from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a compact form: the device's operation intervals (``ops``, from
each device plane's ``XLA Ops`` line), its program executions
(``modules``, the ``XLA Modules`` line), and the harness's own host
spans (``spans``, the events named ``bench.*``), all in nanoseconds on
the profiler's one clock. Everything else works on that form, so the
arithmetic is checked on a trace written by hand.
"""
from __future__ import annotations

import glob
import os

import numpy as np

SPAN_PREFIX = "bench."
SWEEP = "bench.sweep"
STAGES = ("bench.build", "bench.engine", "bench.rollup")


def _short(name: str) -> str:
    """An HLO instruction's name without its text (``%fusion.12 =
    (...) fusion(...)`` -> ``%fusion.12``)."""
    return name.split(" = ", 1)[0]


def from_xplane(trace_dir: str) -> dict:
    """The compact form of the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    pd = ProfileData.from_file(paths[0])
    out = {"ops": [], "modules": [], "spans": [], "devices": 0}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines and "XLA Modules" not in lines:
                continue
            out["devices"] += 1
            for key, name in (("ops", "XLA Ops"),
                              ("modules", "XLA Modules")):
                if name in lines:
                    out[key] += [[e.start_ns, e.duration_ns,
                                  _short(e.name)]
                                 for e in lines[name].events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                out["spans"] += [[e.start_ns, e.duration_ns, e.name]
                                 for e in ln.events
                                 if e.name.startswith(SPAN_PREFIX)]
    for key in ("ops", "modules", "spans"):
        out[key].sort()
    return out


def union(intervals) -> np.ndarray:
    """Merged ``[start, end]`` rows of ``[start, dur, ...]`` intervals."""
    if not len(intervals):
        return np.zeros((0, 2))
    s = np.array([r[0] for r in intervals], np.float64)
    e = s + np.array([r[1] for r in intervals], np.float64)
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    first = np.flatnonzero(np.r_[True, s[1:] > e[:-1]])
    last = np.r_[first[1:] - 1, len(s) - 1]
    return np.stack([s[first], e[last]], axis=1)


def overlap(merged: np.ndarray, a: float, b: float) -> float:
    """Length of ``[a, b]`` that the merged rows cover."""
    if not len(merged):
        return 0.0
    d = np.minimum(merged[:, 1], b) - np.maximum(merged[:, 0], a)
    return float(np.clip(d, 0.0, None).sum())


def busy(tr: dict) -> np.ndarray:
    """Merged intervals in which an operation ran on the device: the
    ``XLA Ops`` line where the trace has one, else program executions.
    Kept in the trace under ``_busy`` once worked out."""
    if "_busy" not in tr:
        tr["_busy"] = union(tr["ops"] or tr["modules"])
    return tr["_busy"]


def spans(tr: dict, name: str) -> list:
    return [(s, s + d) for s, d, n in tr["spans"] if n == name]


def window(tr: dict):
    """``(start, end)`` of the traced window: the first sweep's start to
    the last sweep's end; None when the trace holds no sweep."""
    sw = spans(tr, SWEEP)
    if not sw:
        return None
    return sw[0][0], max(e for _, e in sw)


def busy_share(tr: dict):
    """``(busy_ns, window_ns)`` inside the traced window."""
    w = window(tr)
    if w is None:
        return None
    return overlap(busy(tr), *w), w[1] - w[0]


def mean_span_ms(tr: dict, name: str):
    sp = spans(tr, name)
    if not sp:
        return None
    return sum(e - s for s, e in sp) / len(sp) / 1e6


def host_share_ms(tr: dict, name: str):
    """Mean time per span ``name`` in which the device was idle: the
    span's own host work, net of the device time inside it."""
    sp = spans(tr, name)
    if not sp:
        return None
    merged = busy(tr)
    return sum((e - s) - overlap(merged, s, e) for s, e in sp) \
        / len(sp) / 1e6


def top_ops(tr: dict, k: int = 10) -> list:
    """The ``k`` device operations that took most time, in seconds."""
    tot = {}
    for _, d, n in tr["ops"] or tr["modules"]:
        tot[n] = tot.get(n, 0.0) + d
    return [[n, t / 1e9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: dict, k: int = 10) -> list:
    """The ``k`` longest stretches of the window with no device
    operation, in seconds, each named by the host stage that covers
    most of it (``between sweeps`` where none does)."""
    w = window(tr)
    if w is None:
        return []
    b = busy(tr)
    b = b[(b[:, 1] > w[0]) & (b[:, 0] < w[1])]
    gaps, t = [], w[0]
    for s, e in b.tolist() + [[w[1], w[1]]]:
        s, e = max(s, w[0]), min(e, w[1])
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    stage = {n: union([[a, b - a] for a, b in spans(tr, n)])
             for n in STAGES}
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        cover = {n: overlap(m, a, b) for n, m in stage.items()}
        best = max(cover, key=cover.get)
        out.append([best if cover[best] > 0 else "between sweeps",
                    (b - a) / 1e9])
    return out
