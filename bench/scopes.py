"""Device time by the program's own scopes.

The program names the layers of its jitted train step (``jax.named_scope``,
DESIGN.md §16 of the program); each HLO instruction carries the stack of
names it was traced under in its ``op_name`` (``bench/hlo.py`` keeps it).
``scope_of`` reduces such a stack to one scope: the innermost program scope
in it, where a stage (``intra``, ``cross``) is kept together with the
``hetccl.<op>.<size_class>.<variant>`` dispatch it belongs to, and a stack
with none is ``unscoped``.  So an op under ``optimizer/.../hetccl.*`` counts
as the collective, not as the optimizer, and the attention core's backward
under ``attention/attention_bwd`` as ``attention_bwd``.  Wrappers that JAX
puts around names (``jvp(...)``, ``transpose(...)``) are taken off first.

``by_scope`` sums the traced steps' core-line device time by scope;
``table`` lays out the whole attribution of one traced run, which the
``scoped_op_share`` reader writes to stderr in every traced run.
"""
from __future__ import annotations

import re

LAYERS = ("attention", "attention_bwd", "mlp", "lm_head", "embed",
          "optimizer", "step_stats")
STAGES = ("intra", "cross")
HETCCL = re.compile(r"hetccl\.[a-z_]+\.(small|medium|large)\.[a-z]+")
UNSCOPED = "unscoped"
_WRAPPER = re.compile(r"[A-Za-z_]+\(|\)")


def scope_of(op_name: str) -> str:
    """The program scope ``op_name`` is attributed to (see the module)."""
    names = [c for c in (_WRAPPER.sub("", part) for part in op_name.split("/"))
             if c in LAYERS or c in STAGES or HETCCL.fullmatch(c)]
    if not names:
        return UNSCOPED
    inner = names[-1]
    if inner in STAGES:
        outer = [c for c in names[:-1] if HETCCL.fullmatch(c)]
        if outer:
            return f"{outer[-1]}/{inner}"
    return inner


def _ops(run):
    """Each core-line operation of the traced steps as (scope, label, ns
    per device per traced step, op, ``op_name``).  An operation the
    compiled step does not hold (another program's) is ``unscoped``."""
    red = run.reduction
    per = red.n_devices * red.steps
    for key, rec in red.ops.items():
        ins = run.instrs.get(key)
        if ins is None:
            yield UNSCOPED, f"{key[1]} ({key[0] or 'no module'})", rec["ns"] / per, key[1], ""
        else:
            yield scope_of(ins.op_name), ins.label, rec["ns"] / per, key[1], ins.op_name


def _traced(run) -> bool:
    return run.reduction is not None and bool(run.reduction.steps)


def by_scope(run) -> dict[str, float]:
    """Device ns per device per traced step, by scope, of the traced
    steps' core-line operations (``run.reduction.ops``).  Empty when the
    run has no trace."""
    out: dict[str, float] = {}
    if _traced(run):
        for scope, _, ns, _, _ in _ops(run):
            out[scope] = out.get(scope, 0.0) + ns
    return out


def ms_per_step(run, keep) -> float | None:
    """ms a traced step of the scopes ``keep(scope)`` selects, or None when
    the run has no trace or the program names none of them."""
    found = [ns for scope, ns in by_scope(run).items() if keep(scope)]
    return sum(found) * 1e-6 if found else None


def table(run, top: int | None = None) -> dict | None:
    """The whole attribution of one traced run: per scope, ms a step, its
    share of the summed op time, and its operations by the breakdown's
    labels, longest first (the ``top`` longest); the unscoped operations
    with their ``op_name``s, longest first (the ``top`` longest).  None
    when the run has no trace."""
    if not _traced(run):
        return None
    red = run.reduction
    rows: dict = {}
    unscoped = []
    for scope, label, ns, op, op_name in _ops(run):
        ms = ns * 1e-6
        row = rows.setdefault(scope, {"ms": 0.0, "ops": {}})
        row["ms"] += ms
        row["ops"][label] = row["ops"].get(label, 0.0) + ms
        if scope == UNSCOPED:
            unscoped.append([ms, op, op_name])
    total = sum(r["ms"] for r in rows.values())
    for row in rows.values():
        row["share"] = row["ms"] / total if total else 0.0
        row["ops"] = sorted(row["ops"].items(), key=lambda kv: -kv[1])[:top]
    per = red.n_devices * red.steps
    return {"op_ms_per_step": total,
            "busy_ms_per_step": sum(red.busy_ns) / per * 1e-6,
            "window_ms_per_step": red.window_ns / red.steps * 1e-6,
            "scopes": dict(sorted(rows.items(), key=lambda kv: -kv[1]["ms"])),
            "unscoped": sorted(unscoped, reverse=True)[:top]}
