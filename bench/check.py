"""The comparison that decides ``correct`` for a training cell.

The program and the plain reference train from the same weights on the
same batches for the cell's check steps.  Four numbers are compared, each
against a limit of its own (``bench/limits/<cell>.json``):

- ``loss``: the largest relative gap of a step's mean loss,
  ``|program - reference| / reference``, over the check steps;
- ``grad``: the worst leaf's gap between the norms of the first step's
  gradient as the optimizer gets it, ``|program - reference|`` over the
  larger of the reference's norm of that leaf and of the median leaf;
- ``delta``: the same for the norm of the weights' change after the last
  check step, over the leaves whose first reference gradient is at least a
  thousandth of the median leaf's (a leaf with no gradient moves by
  round-off alone);
- ``param``: the same for the change of the parameters the next step
  computes with (the program's, gathered across chips; the reference's
  master weights cast to the configuration's dtype), over the same leaves.

A leaf is one layer's slice of a stacked parameter, or a whole parameter;
which parameters are stacked, the configuration's family says
(``bench/weights.py`` ``stacked``).
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss", "grad", "delta", "param")
MOVING = 1e-3            # a leaf moves if its reference gradient >= this x median


def _flat(per_leaf: dict, stacked) -> dict[str, float]:
    out = {}
    for name, vec in per_leaf.items():
        vec = np.atleast_1d(vec)
        for i, x in enumerate(vec):
            out[f"{name}[{i}]" if name in stacked else name] = float(x)
    return out


def worst_leaf(prog: dict, ref: dict, stacked, keep=None) -> tuple[float, str]:
    """(gap, leaf) of the leaf whose norms differ most, relative to the
    larger of its reference norm and the median leaf's; a non-finite
    program norm reads infinite.  ``stacked`` names the parameters whose
    norms are one per layer."""
    p, r = _flat(prog, stacked), _flat(ref, stacked)
    names = [n for n in r if keep is None or n in keep]
    med = float(np.median([r[n] for n in names]))

    def gap(n):
        g = abs(p[n] - r[n]) / max(r[n], med)
        return g if math.isfinite(g) else math.inf

    where = max(names, key=gap)
    return gap(where), where


def gaps(prog: dict, ref: dict, stacked) -> dict[str, tuple[float, str]]:
    """``{number: (value, where)}`` for readings of the program (or of a
    control put in its place) against the reference's; ``stacked`` names
    the parameters whose norms are one per layer."""
    loss = max((abs(p - r) / abs(r) if math.isfinite(p) else math.inf, f"step {i}")
               for i, (p, r) in enumerate(zip(prog["loss"], ref["loss"])))
    g = _flat(ref["grad"], stacked)
    med = float(np.median(list(g.values())))
    moving = {n for n, x in g.items() if x >= MOVING * med}
    return {"loss": loss, "grad": worst_leaf(prog["grad"], ref["grad"], stacked),
            "delta": worst_leaf(prog["delta"], ref["delta"], stacked, moving),
            "param": worst_leaf(prog["param"], ref["param"], stacked, moving)}


def verdict(found: dict, limits: dict | None) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}).  Every number that the
    limits name has to be finite and at most its limit; without limits
    nothing is correct."""
    shown = {}
    ok = limits is not None
    for name in NUMBERS:
        value = found[name][0]
        limit = None if limits is None else limits.get(name)
        shown[f"{name}_gap"] = {"value": value, "limit": limit}
        if limit is not None and not (math.isfinite(value) and value <= limit):
            ok = False
    return ok, shown
