"""The work a step and a kernel call require, and the chip's peaks.

These functions are the benchmark's yardstick: model FLOPs per trained
token (for ``step_mfu``; each family counts its own block) and the FLOPs
and bytes one call of a kernel requires (for ``<kernel>_roofline``).  They
count the work the algorithm needs, not what an implementation happens to
do: recomputation, masked blocks and re-reads are not counted, so a share
computed from them cannot pass 100 % unless the time leaves out part of
the work.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

from bench import families

PEAKS = Path(__file__).with_name("peaks.json")


def peak(device_kind: str) -> dict:
    """``{"bf16_flops", "hbm_bytes_per_s", "hbm_bytes"}`` of one chip.  An
    unknown device is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(k for k in table if not k.startswith('_'))}")
    return table[device_kind]


def model_flops_per_token(arch: dict, seq: int) -> float:
    """Forward and backward FLOPs one trained token requires, as the
    configuration's family counts them (``bench/families/``)."""
    return families.of(arch).model_flops_per_token(arch, seq)


def flash_fwd_work(q_shape, k_shape, q_bytes: int, kv_bytes: int,
                   o_bytes: int, causal: bool = True) -> tuple[float, float]:
    """(FLOPs, bytes) that one causal attention forward requires, for q of
    shape (B, H, Sq, d) and k, v of shape (B, Hkv, Sk, d) in the kernel's
    layout: 4 * B * H * Sq * Sk * d, halved when causal; q, k, v read once
    and o written once, at their dtypes' sizes."""
    b, h, sq, d = q_shape
    sk = k_shape[2]
    flops = 4.0 * b * h * sq * sk * d * (0.5 if causal else 1.0)
    nbytes = (math.prod(q_shape) * q_bytes + 2 * math.prod(k_shape) * kv_bytes
              + math.prod(q_shape) * o_bytes)
    return flops, float(nbytes)
