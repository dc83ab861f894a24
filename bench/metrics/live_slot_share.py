"""Share of the device's while-loop work that served live tasks: the
counter ``live_slots`` (the task slots arrived and not done at each
trip's start, summed over the trips) over ``iters`` x ``n_slots``, over
every cell of the window's sweeps. A trip sorts, masks and selects over
all N slots of its bucket, so the share says how much of that work a
long trace's padding and finished tasks leave to the live ones. None
where the engine does not count live slots (``mc_stats`` without
``live_slots``)."""


def read(run):
    live = slots = 0
    for sw in run.sweeps:
        for r in sw.results:
            if r is None:
                continue
            stats = r.mc_stats
            if "live_slots" not in stats:
                return None
            live += stats["live_slots"]
            slots += stats["iters"] * stats["n_slots"]
    return live / slots if slots else None
