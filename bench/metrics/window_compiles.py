"""Backend compiles (persistent-cache loads included) inside the
measured window, from JAX's monitoring events. Set-up warms every
shape, so this should read 0."""


def read(run):
    return run.compile["window_events"]
