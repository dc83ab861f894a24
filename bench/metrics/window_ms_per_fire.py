"""Device self ms of the ``mc_window_ff`` scope per (trip, core) firing
of the window fast-forward, over the whole sweeps ``scopes.read`` keeps.

The engine counts firings per cell (``mc_stats["fires_by_layer"]``),
not per sweep of the trace. Every sweep of a run repeats the same plan,
the same cells in the same order, so each sweep fires the window
exactly as often: the firings of one sweep times the number of whole
sweeps is the firings of those sweeps. None where the engine does not
count firings, the trace has no whole sweep, or the window never
fired."""
from benchkit import scopes


def read(run):
    got = scopes.read(run)
    if got is None or not run.sweeps:
        return None
    results = [r for r in run.sweeps[0].results if r is not None]
    if any("fires_by_layer" not in r.mc_stats for r in results):
        return None
    fires = sum(r.mc_stats["fires_by_layer"]["window_ff"] for r in results)
    if not fires:
        return None
    return got["ms"]["mc_window_ff"] / (fires * got["sweeps"])
