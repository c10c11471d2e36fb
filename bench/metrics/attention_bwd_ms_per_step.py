"""Device time of the attention core's backward per traced step, in ms:
the core-line operations the program names ``attention_bwd`` (the custom
VJP's backward of the flash attention, ``bench/scopes.py``), summed over
the traced steps, averaged over the cell's devices.  The projections' and
RoPE's backward are the ``attention`` layer's, not this.  Nothing is
returned when the program names no such scope."""
from bench import scopes


def read(run):
    return scopes.ms_per_step(run, lambda s: s == "attention_bwd")
