"""Host time per sweep inside ``run_scenarios`` while the device is
idle: packing the batch, copying it in and out, and rebuilding
``Task``/``SimResult`` objects. The ``bench.engine`` span net of the
device's busy time inside it."""
from benchkit import tracefile


def read(run):
    if run.trace is None:
        return None
    return tracefile.host_share_ms(run.trace, "bench.engine")
