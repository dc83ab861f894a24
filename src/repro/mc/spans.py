"""Profiler spans and device scopes of the batched Monte-Carlo engine.

The engine marks its own layers in ``jax.profiler``'s trace, on the
profiler's one clock, so a trace of a sweep says where the host and the
device spend their time:

* host spans ``mc.<name>`` (:func:`span`), one per program or per call,
  never per task or per cell; keyword ids become the event's stats;
* ``mc.gc`` spans around every garbage collection while
  :func:`gc_spans` is open;
* device scopes (:data:`SCOPES`), ``jax.named_scope``s that the kernel
  wraps around each retirement layer of its while-loop body; an op's
  layer is the outermost ``mc_`` segment of its HLO ``op_name``.

The kernel also counts, per cell, the events each retirement layer
retires (``events_by_layer``, in the order of :data:`EVENT_LAYERS`), the
(trip, core) firings of each multi-event layer (``fires_by_layer``, in
the order of :data:`FIRE_LAYERS`), the deepest CFS runqueue at the
end of a trip (``rq_depth_max``), the trips that began with a CFS
runqueue deeper than ``kernels._CYCLE_K - 1`` (``rotation_deep_trips``),
the trips on which its batch ran the window fast-forward
(``window_trips``) and, summed over its trips, the task slots live
(arrived and not done) at a trip's start (``live_slots``), so that
``live_slots`` over trips x N is the share of a trip's ``[C, N]``
sorts, masks and selects that serves live tasks.
``rotation_deep_trips`` counts where the rotation's pop prefix does not
serve: a batch runs the full sort
(``kernels.rotation_sorted``) on a trip if and only if some live cell
of the batch is deep at its start, and a cell's count is its own,
whatever batch it runs in. A batch runs the window on a trip if and
only if it retires a chunk on some core of some live cell, so
``window_trips``, unlike the others, is the batch's.

Outside a profiler session a span costs one small object.
"""
from __future__ import annotations

import contextlib
import gc

import jax

SPAN_PREFIX = "mc."
SCOPES = ("mc_rotation", "mc_cycle_ff", "mc_window_ff", "mc_advance",
          "mc_micro")
EVENT_LAYERS = ("cycle_ff", "window_ff", "advance", "micro")
FIRE_LAYERS = ("cycle_ff", "window_ff")


def span(name: str, **ids):
    """A host span ``mc.<name>`` whose ``ids`` are stats of the event."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **ids)


@contextlib.contextmanager
def gc_spans():
    """While open, wrap every garbage collection in an ``mc.gc`` span
    (stat ``generation``)."""
    open_ = []

    def hook(phase, info):
        if phase == "start":
            sp = span("gc", generation=info["generation"])
            sp.__enter__()
            open_.append(sp)
        elif open_:
            open_.pop().__exit__(None, None, None)

    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)
