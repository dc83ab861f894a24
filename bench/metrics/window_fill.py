"""Share of the window fast-forward's scanned chunks that retired work:
the events the ``window_ff`` layer retired over its (trip, core)
firings times the window length W, over every cell of the window's
sweeps (all whole: a run finishes the sweep in flight). A firing scans
W chunks of its core's rotation on every trip it runs, and retires at
most W of them, so the share lies in (0, 1]. None where the engine does
not count firings (``mc_stats`` without ``fires_by_layer``) or the
window never fired."""


def read(run):
    events = slots = 0
    for sw in run.sweeps:
        for r in sw.results:
            if r is None:
                continue
            stats = r.mc_stats
            if "fires_by_layer" not in stats:
                return None
            events += stats["events_by_layer"]["window_ff"]
            slots += stats["fires_by_layer"]["window_ff"] * \
                stats["window_len"]
    return events / slots if slots else None
