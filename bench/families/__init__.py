"""Block families of the benchmark's configurations, one module each,
named by the ``family`` key of a configuration file.

A family module holds everything the harness knows about a block:

- ``LAYOUT``: canonical leaf name -> ``(shape, init, stacked)``, where
  ``shape(arch)`` gives the leaf's shape from the configuration, ``init``
  is ``"normal"`` (``normal(0, initializer_range)``) or ``"ones"``, and a
  stacked leaf holds one slice per layer along its first axis;
- ``PATHS``: canonical leaf name -> path in the program's params;
- ``model_config(arch)``: the program's ``ModelConfig`` of the
  configuration (called through ``bench/program.py``, which puts the
  program on the import path first);
- ``model_flops_per_token(arch, seq)``: the model FLOPs one trained token
  requires, forward and backward, with no recomputation and no masked
  blocks (``bench/work.py``);
- ``TINY``: overrides of the configuration that shrink it for the CPU
  tests.

A configuration of a new block brings its own module here (and its plain
reference under ``bench/references/``); no other file of the harness
names a leaf.
"""
from __future__ import annotations

import importlib


def of(arch: dict):
    """The family module that the configuration ``arch`` names."""
    return importlib.import_module(f"{__name__}.{arch['family']}")
