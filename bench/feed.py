"""The benchmark's training data: seekable pseudo-text from the seed.

A copy of the program's ``synthetic_batch`` (``src/repro/data/
pipeline.py``), kept here so that no change to the program can move the
data a cell trains on.  Every token is a pure function of ``(seed, step,
row, position)``, so all rows of all steps differ and the same seed gives
the same batches.
"""
from __future__ import annotations

import numpy as np


def synthetic_batch(seed: int, step: int, n_micro: int, global_mb: int,
                    seq: int, vocab: int) -> dict:
    """``{"tokens", "labels"}``, each ``(n_micro, global_mb, seq)`` int32;
    the labels are the tokens shifted by one position."""
    rows = n_micro * global_mb
    with np.errstate(over="ignore"):              # intended u64 wraparound
        base = (np.uint64(seed % 2**64) * np.uint64(0x9E3779B97F4A7C15)
                + np.uint64(step + 1))
        row_keys = (np.arange(rows, dtype=np.uint64) + np.uint64(1)) * np.uint64(
            0xBF58476D1CE4E5B9) + base
        pos = np.arange(seq + 1, dtype=np.uint64)
        z = row_keys[:, None] + pos[None, :] * np.uint64(0x94D049BB133111EB)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
        toks = (z % np.uint64(vocab)).astype(np.int32)
    return {"tokens": toks[:, :-1].reshape(n_micro, global_mb, seq),
            "labels": toks[:, 1:].reshape(n_micro, global_mb, seq)}


def job_batch(seed: int, step: int, arch: dict, job: dict) -> dict:
    """Step ``step``'s global batch of a training job: ``n_micro``
    micro-steps of ``micro_batch`` rows per data-parallel rank."""
    dp = job["mesh"].get("pod", 1) * job["mesh"].get("data", 1)
    return synthetic_batch(seed, step, job["n_micro"], job["micro_batch"] * dp,
                           job["seq"], arch["vocab_size"])


def tokens_per_step(job: dict) -> int:
    dp = job["mesh"].get("pod", 1) * job["mesh"].get("data", 1)
    return job["n_micro"] * job["micro_batch"] * dp * job["seq"]
