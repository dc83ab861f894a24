"""Host time per sweep inside the program's ``mc.launch`` spans while
the device is idle: the copy in, dispatch and ``device_get``
(``progtrace``)."""
from benchkit import progtrace


def read(run):
    if run.trace is None:
        return None
    return progtrace.launch_host_ms(run.trace)
