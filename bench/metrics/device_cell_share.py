"""Share of the window's cells that ran on the device: cells whose
result says ``backend == "jax"`` over cells attempted. The gate, or a
program that raised, sends the rest nowhere."""


def read(run):
    attempted = sum(sw.n_cells for sw in run.sweeps)
    if not attempted:
        return None
    return sum(sw.device_cells for sw in run.sweeps) / attempted
