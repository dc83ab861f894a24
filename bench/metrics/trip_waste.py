"""Share of the device's while-loop trips spent on cells already done:
a vmapped batch runs until its slowest cell drains. Cells are grouped
into programs by the engine's own shape buckets."""
from benchkit import counters


def read(run):
    return counters.trip_waste([p for sw in run.sweeps
                                for p in sw.programs])
