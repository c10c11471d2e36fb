"""Device time of HetCCL's cross-island stage per traced step, in ms: the
core-line operations the program names ``cross`` under a
``hetccl.<op>.<size_class>.<variant>`` dispatch scope (``bench/scopes.py``;
on the Pallas backend, the DMA ring kernels and the work around them),
averaged over the cell's devices.  Core-line events only: the async line is
not in the reduction's operations, so a cross stage on XLA's collectives
would show only the ``-start``/``-done`` halves it runs on the core.
Nothing is returned when the program names no such stage."""
from bench import scopes


def read(run):
    return scopes.ms_per_step(
        run, lambda s: s.startswith("hetccl.") and s.endswith("/cross"))
