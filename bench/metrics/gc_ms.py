"""Host time per sweep in garbage collections inside ``run_scenarios``:
the program's ``mc.gc`` spans in the traced window (``progtrace``)."""
from benchkit import progtrace


def read(run):
    if run.trace is None:
        return None
    return progtrace.span_ms(run.trace, "mc.gc")
