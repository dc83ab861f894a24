"""Events the ``micro`` layer retired in the traced window (the engine's
``events_by_layer`` counter) per ms of device self time in its scope,
``mc_micro``."""
from benchkit import scopes


def read(run):
    return scopes.events_per_ms(run, "micro")
