"""Seconds of backend compilation, or of loading programs from the
persistent cache, during set-up, from JAX's monitoring events."""


def read(run):
    return run.compile["setup_s"]
