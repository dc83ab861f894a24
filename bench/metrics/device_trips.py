"""While-loop trips the device ran per sweep: over the sweep's
programs, each program's largest cell ``n_iters``."""
from benchkit import counters


def read(run):
    if not run.sweeps:
        return None
    return sum(counters.device_trips(sw.programs)
               for sw in run.sweeps) / len(run.sweeps)
