"""Host time per sweep writing the device's answers into ``Task``s and
building the results: the program's ``mc.rebuild`` spans in the traced
window (``progtrace``)."""
from benchkit import progtrace


def read(run):
    if run.trace is None:
        return None
    return progtrace.span_ms(run.trace, "mc.rebuild")
