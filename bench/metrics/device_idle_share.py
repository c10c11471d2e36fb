"""The share of the traced window in which no op ran on a device, in %:
1 - (union of the device's op intervals / window), computed per device and
averaged over the cell's devices.  The window runs from the first traced
step's start on the host to the last one's end."""


def read(run):
    red = run.reduction
    if red is None:
        return None
    busy = sum(red.busy_ns) / red.n_devices
    return 100.0 * (1.0 - busy / red.window_ns)
