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

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compat, tacc
from repro.kernels import ref
from repro.kernels.collective_reduce import collective_reduce as _cr_pallas
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.grouped_matmul import grouped_matmul as _gmm_pallas
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
                    interpret=False, bq=128, bk=128):
    """Model-layout wrapper for the Pallas flash kernel.

    Decode (Sq < 8) and offset cases fall back to the chunked-jnp path —
    the kernel targets the big training/prefill shapes.  Differentiable:
    see :func:`_flash` for the backward.
    """
    from repro.models.attention import chunked_attention
    B, Sq, Hq, d = q.shape
    if Sq < 8 or q_offset != 0 or k_offset != 0:
        return chunked_attention(q, k, v, kind=kind, window=window,
                                 q_offset=q_offset, k_offset=k_offset,
                                 k_len=k_len, chunk=chunk or 512, scale=scale)
    cfg = (kind, window, k_len, chunk or 512, scale, bq, bk, interpret)
    return _flash(q, k, v, cfg)


def _flash_manual(q, k, v, cfg):
    """The Pallas forward, in a region where every mesh axis is manual
    (Mosaic kernels cannot be auto-partitioned): heads split over the auto
    axes when both head counts divide, otherwise every auto-axis rank runs
    the whole call."""
    kind, window, k_len, _, scale, bq, bk, interpret = cfg
    Sq = q.shape[1]

    def fwd(q, k, v):
        qt = jnp.moveaxis(q, 1, 2)
        kt = jnp.moveaxis(k, 1, 2)
        vt = jnp.moveaxis(v, 1, 2)
        qt, pq = _pad_to(qt, bq, 2)
        kt, _ = _pad_to(kt, bk, 2)
        vt, _ = _pad_to(vt, bk, 2)
        out = flash_attention_fwd(
            qt, kt, vt, kind=kind, window=window,
            k_len=k.shape[1] if k_len is None else k_len, scale=scale,
            bq=bq, bk=bk, interpret=interpret)
        if pq:
            out = out[:, :, :Sq]
        return jnp.moveaxis(out, 1, 2)

    auto = compat.auto_axes()
    n_auto = math.prod(auto.values())
    spec = P()
    if n_auto > 1 and q.shape[2] % n_auto == 0 and k.shape[2] % n_auto == 0:
        spec = P(None, None, tuple(auto), None)
    return compat.manual_region(fwd, in_specs=spec, out_specs=spec)(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg):
    """Flash attention with a custom VJP: the forward is the Pallas kernel;
    the backward is the VJP of ``chunked_attention`` (the same online-softmax
    math in jnp, recomputed from q, k, v) — not a Pallas backward kernel."""
    return _flash_manual(q, k, v, cfg)


def _flash_fwd(q, k, v, cfg):
    return _flash_manual(q, k, v, cfg), (q, k, v)


def _flash_bwd(cfg, res, g):
    from repro.models.attention import chunked_attention
    kind, window, k_len, chunk, scale = cfg[:5]
    _, vjp = jax.vjp(functools.partial(
        chunked_attention, kind=kind, window=window, k_len=k_len,
        chunk=chunk, scale=scale), *res)
    return vjp(g)


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
