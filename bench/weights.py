"""Seeded weights of a decoder in the benchmark's own canonical layout.

Both the program's state (``bench/program.py``) and the plain reference
(``bench/references/``) start from these weights, so the reference takes
nothing that the program made.  Initialisation follows the published
Llama recipe: every matrix and the embedding ``normal(0,
initializer_range)``, every RMSNorm scale 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# canonical name -> (shape builder, init); "layers/*" are stacked (L, ...)
_LAYOUT = {
    "embed": (lambda a: (a["vocab_size"], a["hidden_size"]), "normal"),
    "final_norm": (lambda a: (a["hidden_size"],), "ones"),
    "lm_head": (lambda a: (a["hidden_size"], a["vocab_size"]), "normal"),
    "layers/ln1": (lambda a: (_L(a), a["hidden_size"]), "ones"),
    "layers/ln2": (lambda a: (_L(a), a["hidden_size"]), "ones"),
    "layers/wq": (lambda a: (_L(a), a["hidden_size"], _H(a), _hd(a)), "normal"),
    "layers/wk": (lambda a: (_L(a), a["hidden_size"], _KV(a), _hd(a)), "normal"),
    "layers/wv": (lambda a: (_L(a), a["hidden_size"], _KV(a), _hd(a)), "normal"),
    "layers/wo": (lambda a: (_L(a), _H(a), _hd(a), a["hidden_size"]), "normal"),
    "layers/w1": (lambda a: (_L(a), a["hidden_size"], a["intermediate_size"]), "normal"),
    "layers/w3": (lambda a: (_L(a), a["hidden_size"], a["intermediate_size"]), "normal"),
    "layers/w2": (lambda a: (_L(a), a["intermediate_size"], a["hidden_size"]), "normal"),
}


def _L(a):
    return a["num_hidden_layers"]


def _H(a):
    return a["num_attention_heads"]


def _KV(a):
    return a["num_key_value_heads"]


def _hd(a):
    return a.get("head_dim") or a["hidden_size"] // a["num_attention_heads"]


def names() -> list[str]:
    return sorted(_LAYOUT)


def shapes(arch: dict) -> dict[str, tuple[int, ...]]:
    return {n: _LAYOUT[n][0](arch) for n in names()}


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
    out = {}
    for i, name in enumerate(names()):
        shape = _LAYOUT[name][0](arch)
        if _LAYOUT[name][1] == "ones":
            out[name] = jnp.ones(shape, dtype)
        else:
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
    return out
