"""Seeded weights of a model in the benchmark's own canonical layout.

Both the program's state (``bench/program.py``) and the plain reference
(``bench/references/``) start from these weights, so the reference takes
nothing that the program made.  The leaves, their shapes and their
initialisation are the configuration's family's ``LAYOUT``
(``bench/families/``): a ``"normal"`` leaf is drawn ``normal(0,
initializer_range)``, a ``"ones"`` leaf is all ones.  Each leaf is drawn
from the seed folded with its position among the sorted canonical names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import families


def names(arch: dict) -> list[str]:
    return sorted(families.of(arch).LAYOUT)


def shapes(arch: dict) -> dict[str, tuple[int, ...]]:
    layout = families.of(arch).LAYOUT
    return {n: layout[n][0](arch) for n in names(arch)}


def stacked(arch: dict) -> frozenset[str]:
    """The leaves that hold one slice per layer along their first axis."""
    return frozenset(n for n, (_, _, per_layer) in families.of(arch).LAYOUT.items()
                     if per_layer)


def seed_words(seed: int) -> tuple[np.uint32, np.uint32]:
    """A seed of up to 64 bits as two uint32 words, so that seeds past
    2**31 work without JAX's 64-bit mode."""
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def seed_key(lo, hi):
    """The PRNG key of a seed given as :func:`seed_words` (traceable)."""
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def make(arch: dict, key, dtype) -> dict[str, jax.Array]:
    """Every canonical leaf, drawn from ``key``, in ``dtype``.  Trace it
    inside one ``jit`` so that the weights are made on the device."""
    std = float(arch["initializer_range"])
    layout = families.of(arch).LAYOUT
    out = {}
    for i, name in enumerate(names(arch)):
        build, init, _ = layout[name]
        shape = build(arch)
        if init == "ones":
            out[name] = jnp.ones(shape, dtype)
        else:
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
    return out
