"""Device self time per while-loop trip of the ``mc_cycle_ff`` scope: its
ops' self time in the traced window over the trips run there."""
from benchkit import scopes


def read(run):
    return scopes.ms_per_trip(run, "mc_cycle_ff")
