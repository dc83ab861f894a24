"""Host time per sweep filling the batch's ``[B, N]`` arrays: the
program's ``mc.pack`` spans in the traced window (``progtrace``)."""
from benchkit import progtrace


def read(run):
    if run.trace is None:
        return None
    return progtrace.span_ms(run.trace, "mc.pack")
