"""Share of the device's while-loop trips on which a cell began with a
CFS runqueue deeper than the rotation's 7-entry pop prefix: the counter
``rotation_deep_trips`` over ``iters``, over every cell of the window's
sweeps. A batch runs the full sort on a trip if and only if one of its
live cells is deep then, so the share says how often a cell needs the
sort, not how often its batch ran it. None where the engine does not
count deep trips (``mc_stats`` without ``rotation_deep_trips``)."""


def read(run):
    deep = trips = 0
    for sw in run.sweeps:
        for r in sw.results:
            if r is None:
                continue
            stats = r.mc_stats
            if "rotation_deep_trips" not in stats:
                return None
            deep += stats["rotation_deep_trips"]
            trips += stats["iters"]
    return deep / trips if trips else None
