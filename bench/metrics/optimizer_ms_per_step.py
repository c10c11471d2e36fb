"""Device time of the optimizer step per traced step, in ms: the
core-line operations the program names ``optimizer`` (clip, global norm,
AdamW over the f32 moments and master, the shard slice) and under no
``hetccl.*`` collective scope (those count as collectives,
``bench/scopes.py``), averaged over the cell's devices.  Nothing is
returned when the program names no such scope."""
from bench import scopes


def read(run):
    return scopes.ms_per_step(run, lambda s: s == "optimizer")
