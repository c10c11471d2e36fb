"""Thin wrappers over the JAX API surface the repo uses.

The repo targets the installed JAX (0.9): ``jax.shard_map`` with
``axis_names``/``check_vma``, ``jax.make_mesh`` with ``axis_types``,
``pltpu.CompilerParams``.  The wrappers keep one spelling at every call site.

:func:`manual_region` is the one piece of policy here: Mosaic (Pallas TPU)
kernels cannot be auto-partitioned, so a kernel traced inside a partially
manual ``shard_map`` (the train step keeps the TP axis 'model' auto) must sit
in a nested ``shard_map`` that makes the remaining mesh axes manual too.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
from jax.sharding import AxisType, PartitionSpec as P


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map``; ``axis_names`` are the *manual* axes (the other
    mesh axes stay auto)."""
    kw: dict[str, Any] = {"check_vma": check_vma}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def set_mesh(mesh):
    """``jax.set_mesh`` (scope for sharding-constraint resolution)."""
    return jax.set_mesh(mesh)


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams``."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)


def make_mesh(shape, axis_names, *, axis_types: str = "auto", devices=None):
    """``jax.make_mesh`` with uniform axis types.

    axis_types: "auto" | "explicit".
    devices: explicit device list to build the mesh over (the elastic
    survivor-mesh path, ``repro.elastic``, DESIGN.md §13): the mesh uses
    exactly these devices, never the default first-N enumeration.
    """
    kw = {} if devices is None else {"devices": list(devices)}
    t = AxisType.Explicit if axis_types == "explicit" else AxisType.Auto
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(t,) * len(tuple(axis_names)), **kw)


def auto_axes() -> dict[str, int]:
    """Non-manual axes of the mesh the current trace runs under, with their
    sizes ({} outside any mesh context or when every axis is manual)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return {}
    return {a: mesh.shape[a] for a in mesh.axis_names
            if a not in mesh.manual_axes}


def manual_region(f, in_specs=P(), out_specs=P()):
    """``f`` run with every mesh axis manual (DESIGN.md §3).

    At call (trace) time, inside a partially manual ``shard_map``, the auto
    axes are wrapped in a nested ``shard_map``; the specs say how operands
    split over them (default ``P()``: replicated — every auto-axis rank runs
    the whole call).  Where no axis is auto, ``f`` runs as it is.  Keyword
    arguments pass through to ``f`` unsharded.
    """
    @functools.wraps(f)
    def call(*args, **kwargs):
        g = functools.partial(f, **kwargs) if kwargs else f
        auto = auto_axes()
        if not auto:
            return g(*args)
        return jax.shard_map(g, in_specs=in_specs, out_specs=out_specs,
                             axis_names=set(auto), check_vma=False)(*args)
    return call
