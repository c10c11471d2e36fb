"""Device time of collectives per traced step, in ms: on each device the
union of the intervals of collective ops (all-reduce, reduce-scatter,
all-gather, collective-permute, all-to-all, their async halves) and of
communicating Pallas kernels (the DMA rings), averaged over the cell's
devices, over the traced steps."""


def read(run):
    red = run.reduction
    if red is None:
        return None
    return sum(red.collective_ns) / red.n_devices / red.steps * 1e-6
