"""The part of the collective time (``collective_ms_per_step``) during
which no other op runs on the same device, per traced step, in ms,
averaged over the cell's devices."""


def read(run):
    red = run.reduction
    if red is None:
        return None
    return sum(red.exposed_ns) / red.n_devices / red.steps * 1e-6
