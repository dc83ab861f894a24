"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals) / window."""
from benchkit import tracefile


def read(run):
    if run.trace is None:
        return None
    bs = tracefile.busy_share(run.trace)
    if bs is None or not bs[1]:
        return None
    return 1.0 - bs[0] / bs[1]
