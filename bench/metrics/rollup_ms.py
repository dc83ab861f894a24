"""Host time per sweep to roll every cell up into its summary row
(``ScenarioResult.summary()``): the mean ``bench.rollup`` span of the
traced window."""
from benchkit import tracefile


def read(run):
    if run.trace is None:
        return None
    return tracefile.mean_span_ms(run.trace, "bench.rollup")
