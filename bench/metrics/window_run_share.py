"""Share of the device's while-loop trips on which a cell's batch ran
the window fast-forward: the counter ``window_trips`` over ``iters``,
over every cell of the window's sweeps. A batch runs the window on a
trip if and only if it retires a chunk on some core of some live cell,
and skips it elsewhere, so the share says how often a cell's trips pay
for the window. None where the engine runs the window on every trip
(``mc_stats`` without ``window_trips``)."""


def read(run):
    ran = trips = 0
    for sw in run.sweeps:
        for r in sw.results:
            if r is None:
                continue
            stats = r.mc_stats
            if "window_trips" not in stats:
                return None
            ran += stats["window_trips"]
            trips += stats["iters"]
    return ran / trips if trips else None
