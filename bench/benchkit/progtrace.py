"""The engine's own host spans in a profiler trace.

``run_scenarios`` writes ``mc.*`` spans (``repro.mc.spans``): ``mc.pack``
per program, ``mc.launch`` per program (copy in, the program,
``device_get``), ``mc.rebuild`` per program and once for the results,
and ``mc.gc`` around each garbage collection. ``from_xplane`` reads
them, with their stats, beside the compact form of
``tracefile.from_xplane`` (left exactly as it is) under
``prog_spans``: ``[start_ns, dur_ns, name, {stat: value}]`` rows.

The harness reads the trace with ``tracefile.from_xplane`` alone, so
these numbers reach a result line only once its runner reads the trace
with ``from_xplane`` here.
"""
from __future__ import annotations

import glob
import os

import numpy as np

from repro.mc.spans import SPAN_PREFIX

from . import tracefile

LAUNCH = SPAN_PREFIX + "launch"


def from_xplane(trace_dir: str) -> dict:
    """``tracefile.from_xplane`` plus ``prog_spans``."""
    from jax.profiler import ProfileData

    tr = tracefile.from_xplane(trace_dir)
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    rows = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                rows += [[e.start_ns, e.duration_ns, e.name, dict(e.stats)]
                         for e in ln.events if e.name.startswith(SPAN_PREFIX)]
    rows.sort(key=lambda r: r[:3])
    tr["prog_spans"] = rows
    return tr


def prog_spans(tr: dict, name: str):
    """``(start, end)`` of the program spans ``name`` inside the traced
    window; None where the trace holds no program span at all (a
    program without them)."""
    rows = tr.get("prog_spans")
    w = tracefile.window(tr)
    if not rows or w is None:
        return None
    return [(s, s + d) for s, d, n, _ in rows
            if n == name and w[0] <= s < w[1]]


def n_sweeps(tr: dict) -> int:
    return len(tracefile.spans(tr, tracefile.SWEEP))


def span_ms(tr: dict, name: str):
    """Mean time per sweep in spans ``name``."""
    sp = prog_spans(tr, name)
    if sp is None:
        return None
    return sum(e - s for s, e in sp) / n_sweeps(tr) / 1e6


def launch_host_ms(tr: dict):
    """Mean time per sweep in ``mc.launch`` spans with the device idle:
    copies, dispatch and ``device_get``."""
    sp = prog_spans(tr, LAUNCH)
    if sp is None:
        return None
    b = tracefile.busy(tr)
    return sum((e - s) - tracefile.overlap(b, s, e) for s, e in sp) \
        / n_sweeps(tr) / 1e6


def _gaps(tr: dict) -> list:
    """``(start, end)`` of every stretch of the window with no device
    operation."""
    w = tracefile.window(tr)
    if w is None:
        return []
    b = tracefile.busy(tr)
    b = b[(b[:, 1] > w[0]) & (b[:, 0] < w[1])]
    gaps, t = [], w[0]
    for s, e in b.tolist() + [[w[1], w[1]]]:
        s, e = max(s, w[0]), min(e, w[1])
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    return gaps


def _merged(rows) -> np.ndarray:
    """Merged ``[start, end]`` rows of ``(start, end)`` intervals."""
    return tracefile.union([[a, b - a] for a, b in rows])


def idle_gaps(tr: dict, k: int = 10) -> list:
    """``tracefile.idle_gaps``, with each gap that a program span covers
    for more than half named ``<stage>/<span>``; where several do, the
    one that covers least of it, the innermost (``mc.gc`` inside
    ``mc.rebuild``)."""
    stage = {n: _merged(tracefile.spans(tr, n))
             for n in tracefile.STAGES}
    names = sorted({r[2] for r in tr.get("prog_spans") or []})
    prog = {n: _merged(prog_spans(tr, n)) for n in names}
    out = []
    for a, b in sorted(_gaps(tr), key=lambda g: g[0] - g[1])[:k]:
        cover = {n: tracefile.overlap(m, a, b) for n, m in stage.items()}
        best = max(cover, key=cover.get)
        label = best if cover[best] > 0 else "between sweeps"
        held = {n: c for n in prog
                if (c := tracefile.overlap(prog[n], a, b)) > (b - a) / 2}
        if held:
            label += "/" + min(held, key=held.get)
        out.append([label, (b - a) / 1e9])
    return out


def launch_clock_share(tr: dict):
    """Share of the window's ``XLA Modules`` time whose execution starts
    inside an ``mc.launch`` span: the host spans and the device share
    one clock where this is near 1."""
    launch = prog_spans(tr, LAUNCH)
    w = tracefile.window(tr)
    if not launch or w is None:
        return None
    m = _merged(launch)
    total = inside = 0.0
    for s, d, _ in tr["modules"]:
        if w[0] <= s < w[1]:
            total += d
            i = np.searchsorted(m[:, 0], s, side="right") - 1
            if i >= 0 and s <= m[i, 1]:
                inside += d
    return inside / total if total else None


def engine_idle_cover(tr: dict):
    """Share of the device-idle time inside ``bench.engine`` spans that
    program spans cover."""
    names = sorted({r[2] for r in tr.get("prog_spans") or []})
    if not names:
        return None
    prog = _merged([iv for n in names for iv in prog_spans(tr, n)])
    engine = _merged(tracefile.spans(tr, "bench.engine"))
    idle = covered = 0.0
    for a, b in _gaps(tr):
        for ea, eb in engine.tolist():
            lo, hi = max(a, ea), min(b, eb)
            if hi > lo:
                idle += hi - lo
                covered += tracefile.overlap(prog, lo, hi)
    return covered / idle if idle else None
