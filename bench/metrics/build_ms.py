"""Host time per sweep to build the workloads (the program's trace
generator, one ``Scenario`` per cell) and route them through the
regime gate: the mean ``bench.build`` span of the traced window."""
from benchkit import tracefile


def read(run):
    if run.trace is None:
        return None
    return tracefile.mean_span_ms(run.trace, "bench.build")
