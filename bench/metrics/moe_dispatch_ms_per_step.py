"""Device time of the expert layer's routing glue per traced step, in ms:
the core-line operations whose ``op_name`` holds the program's
``moe_router``, ``moe_dispatch`` or ``moe_combine`` scope (the router's
scores, top-k and gates, the sort of the (token, expert) pairs into the
experts' buffer and the gather into it, the gates' scatter-add back;
forward, recompute and backward), averaged over the cell's devices.
``bench/scopes.py`` counts these operations as ``mlp``; this reader reads
the sub-scopes themselves.  Nothing is returned when the program names no
such scope."""
import re

SCOPES = frozenset({"moe_router", "moe_dispatch", "moe_combine"})
_WRAPPER = re.compile(r"[A-Za-z_]+\(|\)")


def read(run):
    red = run.reduction
    if red is None or not red.steps:
        return None
    ns, found = 0.0, False
    for key, rec in red.ops.items():
        ins = run.instrs.get(key)
        if ins is None:
            continue
        if {_WRAPPER.sub("", part) for part in ins.op_name.split("/")} & SCOPES:
            ns += rec["ns"]
            found = True
    return ns / (red.n_devices * red.steps) * 1e-6 if found else None
