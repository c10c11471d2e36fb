"""Jit'd kernel wrappers + TACC registration (the per-platform device code).

Paper §4.3: device code is compiled per platform and the right entry point is
resolved at run time.  Here: the Pallas kernels are the TPU entry points, the
pure-jnp refs the CPU ones, and the TACC table picks per platform — callers
(`repro.models.*`) never name a backend.

Wrappers own layout adaptation + padding to MXU-aligned blocks.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compat, tacc
from repro.kernels import ref
from repro.kernels.collective_reduce import collective_reduce as _cr_pallas
from repro.kernels.flash_attention import (LANES, flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.grouped_matmul import grouped_matmul as _gmm_pallas
from repro.kernels.grouped_matmul import gmm_ragged, tgmm_ragged
from repro.kernels.ssd_scan import ssd_scan_pallas


def _pad_to(x, multiple: int, axis: int):
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


# ---------------------------------------------------------------------------
# attention: model layout (B, S, H, d) -> kernel layout (B, H, S, d)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, kind="causal", window=0, q_offset=0,
                    k_offset=0, k_len=None, chunk=None, scale=None,
                    interpret=False, bq=None, bk=None):
    """Model-layout wrapper for the Pallas flash kernels.

    Decode (Sq < 8) and offset cases fall back to the chunked-jnp path —
    the kernels target the big training/prefill shapes.  Differentiable:
    see :func:`_flash` for the backward.  The forward's blocks ``bq``/``bk``
    come from the shapes (``fwd_blocks``) unless given.
    """
    from repro.models.attention import chunked_attention
    B, Sq, Hq, d = q.shape
    if Sq < 8 or q_offset != 0 or k_offset != 0:
        return chunked_attention(q, k, v, kind=kind, window=window,
                                 q_offset=q_offset, k_offset=k_offset,
                                 k_len=k_len, chunk=chunk or 512, scale=scale)
    cfg = _FlashCfg(kind, window, k.shape[1] if k_len is None else k_len,
                    scale, bq, bk, interpret, k.shape[1])
    return _flash(q, k, v, cfg)


class _FlashCfg(NamedTuple):
    kind: str
    window: int
    k_len: int              # valid kv prefix
    scale: float | None
    bq: int | None          # the forward's blocks; None: from the shapes
    bk: int | None
    interpret: bool
    sk: int                 # kv length before padding


def _to_kernel(x, block=None):
    """(B, S, H, d) -> (B, H, S, d), S padded to a multiple of ``block``
    (of 128 when None: the kernels' blocks divide it)."""
    return _pad_to(jnp.moveaxis(x, 1, 2), block or LANES, 2)[0]


def _from_kernel(x, s):
    """(B, H, S_padded, d) -> (B, s, H, d)."""
    return jnp.moveaxis(x[:, :, :s], 1, 2)


def _head_specs(hq, hkv):
    """Specs of the all-manual region the kernels run in (Mosaic kernels
    cannot be auto-partitioned), in model and in kernel layout: heads split
    over the auto axes when both head counts divide, otherwise every
    auto-axis rank runs the whole call."""
    auto = compat.auto_axes()
    n_auto = math.prod(auto.values())
    if n_auto > 1 and hq % n_auto == 0 and hkv % n_auto == 0:
        return P(None, None, tuple(auto), None), P(None, tuple(auto), None, None)
    return P(), P()


def _flash_manual(q, k, v, cfg, residuals=False):
    """The Pallas forward in model layout; with ``residuals`` also the
    backward's residuals (q, k, v, o, lse) in kernel layout, padded."""
    def fwd(q, k, v):
        qt, kt, vt = (_to_kernel(q, cfg.bq), _to_kernel(k, cfg.bk),
                      _to_kernel(v, cfg.bk))
        ot, lse = flash_attention_fwd(
            qt, kt, vt, kind=cfg.kind, window=cfg.window, k_len=cfg.k_len,
            scale=cfg.scale, bq=cfg.bq, bk=cfg.bk, interpret=cfg.interpret)
        out = _from_kernel(ot, q.shape[1])
        return (out, (qt, kt, vt, ot, lse)) if residuals else out

    model, kern = _head_specs(q.shape[2], k.shape[2])
    out_specs = (model, (kern,) * 5) if residuals else model
    return compat.manual_region(fwd, in_specs=model,
                                out_specs=out_specs)(q, k, v)


# Name of the attention core's backward in the compiled program (DESIGN.md
# §16): the ops of the custom VJP's backward carry it in their HLO op_name.
ATTENTION_BWD_SCOPE = "attention_bwd"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg):
    """Flash attention with a custom VJP: the forward is the Pallas kernel
    (its logsumexp output dropped); the backward is the Pallas dK/dV and dQ
    kernels (``flash_attention_bwd``), which recompute the probabilities
    from q, k and the forward's logsumexp."""
    return _flash_manual(q, k, v, cfg)


def _flash_fwd(q, k, v, cfg):
    return _flash_manual(q, k, v, cfg, residuals=True)


def _flash_bwd(cfg, res, g):
    def bwd(qt, kt, vt, ot, lse, g):
        dq, dk, dv = flash_attention_bwd(
            qt, kt, vt, ot, lse, _to_kernel(g, cfg.bq), kind=cfg.kind,
            window=cfg.window, k_len=cfg.k_len, scale=cfg.scale,
            interpret=cfg.interpret)
        return (_from_kernel(dq, g.shape[1]), _from_kernel(dk, cfg.sk),
                _from_kernel(dv, cfg.sk))

    model, kern = _head_specs(g.shape[2], res[1].shape[1])
    with jax.named_scope(ATTENTION_BWD_SCOPE):
        return compat.manual_region(bwd, in_specs=(kern,) * 5 + (model,),
                                    out_specs=(model,) * 3)(*res, g)


_flash.defvjp(_flash_fwd, _flash_bwd)


tacc.register("attention", "tpu")(flash_attention)
tacc.register("attention", "interpret")(
    functools.partial(flash_attention, interpret=True))


# ---------------------------------------------------------------------------
# grouped matmul / expert FFN
# ---------------------------------------------------------------------------

def grouped_matmul(x, w, *, interpret=False, bm=128, bn=128, bk=128):
    G, M, K = x.shape
    _, _, N = w.shape
    xp, pm = _pad_to(x, bm, 1)
    xp, pk = _pad_to(xp, bk, 2)
    wp, _ = _pad_to(w, bk, 1)
    wp, pn = _pad_to(wp, bn, 2)
    out = _gmm_pallas(xp, wp, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:, :M, :N]


def expert_ffn_pallas(buf, w1, w3, w2, *, interpret=False):
    """SwiGLU over the capacity buffer via three grouped matmuls."""
    h1 = grouped_matmul(buf, w1, interpret=interpret)
    h3 = grouped_matmul(buf, w3, interpret=interpret)
    h = jax.nn.silu(h1.astype(jnp.float32)).astype(buf.dtype) * h3
    return grouped_matmul(h, w2, interpret=interpret)


tacc.register("expert_ffn", "tpu")(compat.manual_region(expert_ffn_pallas))
tacc.register("expert_ffn", "interpret")(
    functools.partial(expert_ffn_pallas, interpret=True))
tacc.register("grouped_matmul", "cpu", default=True)(ref.grouped_matmul)
tacc.register("grouped_matmul", "tpu")(compat.manual_region(grouped_matmul))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ragged_gmm(x, w, group_sizes, interpret):
    """Ragged grouped matmul with a custom VJP: the forward and dX are
    ``_gmm_ragged_kernel`` (dX with w transposed), dW is
    ``_tgmm_ragged_kernel``; each runs in an all-manual region."""
    return compat.manual_region(gmm_ragged)(x, w, group_sizes,
                                            interpret=interpret)


def _ragged_gmm_fwd(x, w, group_sizes, interpret):
    return _ragged_gmm(x, w, group_sizes, interpret), (x, w, group_sizes)


def _ragged_gmm_bwd(interpret, res, dy):
    x, w, group_sizes = res
    dx = compat.manual_region(gmm_ragged)(dy, w, group_sizes, transpose_rhs=True,
                                          interpret=interpret)
    dw = compat.manual_region(tgmm_ragged)(x, dy, group_sizes,
                                           interpret=interpret)
    return dx, dw, None


_ragged_gmm.defvjp(_ragged_gmm_fwd, _ragged_gmm_bwd)


def ragged_grouped_matmul(x, w, group_sizes, *, interpret=False):
    """x (R, K) @ w[g] (G, K, N) over groups of rows laid out in order, each
    starting on a multiple of ``grouped_matmul.ROW_TILE`` (``group_sizes``
    are the padded sizes) -> (R, N); differentiable in x and w."""
    return _ragged_gmm(x, w, group_sizes, interpret)


tacc.register("ragged_grouped_matmul", "cpu", default=True)(ref.ragged_grouped_matmul)
tacc.register("ragged_grouped_matmul", "tpu")(ragged_grouped_matmul)
tacc.register("ragged_grouped_matmul", "interpret")(
    functools.partial(ragged_grouped_matmul, interpret=True))


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

def ssd_scan(x, dt, a_cum, B_in, C_in, *, interpret=False):
    return ssd_scan_pallas(x, dt, a_cum, B_in, C_in, interpret=interpret)


tacc.register("ssd_scan_kernel", "cpu", default=True)(ref.ssd_scan)
tacc.register("ssd_scan_kernel", "tpu")(compat.manual_region(ssd_scan))
tacc.register("ssd_scan_kernel", "interpret")(
    functools.partial(ssd_scan, interpret=True))


# ---------------------------------------------------------------------------
# collective local reduction
# ---------------------------------------------------------------------------

def collective_reduce(acc, incoming, *, interpret=False):
    flat_a = acc.reshape(-1)
    flat_b = incoming.reshape(-1)
    L = 256
    pad = (-flat_a.shape[0]) % L
    if pad:
        flat_a = jnp.pad(flat_a, (0, pad))
        flat_b = jnp.pad(flat_b, (0, pad))
    a2 = flat_a.reshape(-1, L)
    b2 = flat_b.reshape(-1, L)
    # ragged row counts are padded inside the kernel wrapper (pad-and-slice)
    out = _cr_pallas(a2, b2, block=(256, L), interpret=interpret)
    out = out.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(acc.shape)


tacc.register("collective_reduce", "cpu", default=True)(ref.collective_reduce)
tacc.register("collective_reduce", "tpu")(
    compat.manual_region(collective_reduce))
tacc.register("collective_reduce", "interpret")(
    functools.partial(collective_reduce, interpret=True))
