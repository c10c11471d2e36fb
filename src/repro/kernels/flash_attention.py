"""Flash attention forward kernel (Pallas TPU).

Online-softmax attention with explicit BlockSpec VMEM tiling:

  grid = (B, Hq, Sq/bq, Sk/bk), dimension_semantics = (parallel, parallel,
  parallel, arbitrary) — the innermost k-block axis is sequential, carrying
  (m, l, acc) in VMEM scratch; the output block is written on the last
  k-step.  GQA is handled in the k/v index_map (kv head = q head // group).

MXU alignment: bq/bk default 128 (q is padded by ops.py when Sq < bq);
head_dim should be a multiple of 128 for full MXU utilization — smaller
head dims still compile but underfill the systolic array.

Masking supports causal, bidirectional and sliding-window.  Block-level
early-exit for fully-masked (q,k) block pairs is expressed with pl.when so
Mosaic can skip the MXU work on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import compat

NEG_INF = -1e30
# The kernel's name in the compiled program and the device trace, where the
# benchmark finds it (``bench/metrics/flash_fwd_roofline.py``); kept fixed.
KERNEL_NAME = "_flash_kernel"


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, kind: str, window: int, bq: int, bk: int,
                  k_len: int):
    ki = pl.program_id(3)
    qi = pl.program_id(2)
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = k_pos < k_len
    if kind == "causal":
        valid &= q_pos >= k_pos
    if window:
        valid &= (q_pos - k_pos) < window

    # block-level skip: causal blocks entirely above the diagonal do no work
    block_live = True
    if kind == "causal":
        block_live = (qi + 1) * bq - 1 >= ki * bk

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, kind: str = "causal", window: int = 0,
                        k_len: int | None = None, scale: float | None = None,
                        bq: int = 128, bk: int = 128,
                        interpret: bool = False):
    """q: (B, Hq, Sq, d);  k, v: (B, Hkv, Sk, d) -> (B, Hq, Sq, d).

    Shapes must be block-aligned (ops.py pads); GQA via Hq = g * Hkv.
    """
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else d ** -0.5
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    grid = (B, Hq, Sq // bq, Sk // bk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, kind=kind, window=window, bq=bq, bk=bk,
        k_len=Sk if k_len is None else k_len)

    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(q, k, v)
