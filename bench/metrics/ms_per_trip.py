"""Device time per while-loop trip: the device's busy time in the
traced window over the trips its programs ran there."""
from benchkit import counters, tracefile


def read(run):
    if run.trace is None:
        return None
    trips = sum(counters.device_trips(sw.programs) for sw in run.sweeps)
    bs = tracefile.busy_share(run.trace)
    if not trips or bs is None or not bs[0]:
        return None
    return bs[0] / 1e6 / trips
