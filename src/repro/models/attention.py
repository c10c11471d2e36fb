"""Attention: chunked online-softmax (flash-style) in pure jnp + KV caches.

This is the memory-sane reference path used for CPU smoke tests and for
dry-run lowering; on TPU the TACC registry dispatches the inner computation to
the Pallas flash-attention kernel (`repro.kernels.flash_attention`).

Supports: causal, bidirectional, sliding-window (SWA), cross-attention,
GQA (kv-head grouping), and decode against a KV cache (single query step).
Softmax statistics accumulate in f32.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import tacc

NEG_INF = -1e30


def _mask(q_pos, k_pos, kind: str, window: int):
    """(Sq, Sk) boolean validity mask from global positions."""
    if kind == "bidir":
        m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    else:
        m = q_pos[:, None] >= k_pos[None, :]
    if window:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


@tacc.register("attention", "cpu", default=True)
def chunked_attention(q, k, v, *, kind: str = "causal", window: int = 0,
                      q_offset=0, k_offset=0, k_len=None, chunk: int = 512,
                      scale: float | None = None):
    """Online-softmax attention over KV chunks.

    q, k: (B, Sq, Hq, hd), (B, Sk, Hkv, hd);  v: (B, Sk, Hkv, dv);
    Hq % Hkv == 0.  q_offset/k_offset: global positions of q[0] / k[0]
    (cache decode uses q_offset = cache_len).  k_len: valid KV prefix length
    (traced ok).  Returns (B, Sq, Hq, dv) in q.dtype.
    """
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    dv = v.shape[-1]
    g = Hq // Hkv
    scale = scale if scale is not None else hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, g, hd)
    chunk = min(chunk, Sk)
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, Hkv, hd)
    vc = v.reshape(B, n_chunks, chunk, Hkv, dv)
    q_pos = q_offset + jnp.arange(Sq)
    kv_valid_len = jnp.asarray(Sk if k_len is None else k_len)

    def body(carry, inp):
        m_prev, l_prev, acc = carry
        kb, vb, c_idx = inp
        k_pos = k_offset + c_idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kb.astype(jnp.float32))
        valid = _mask(q_pos, k_pos, kind, window) & (k_pos < k_offset + kv_valid_len)[None, :]
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (
        jnp.full((B, Hkv, g, Sq), NEG_INF, jnp.float32),
        jnp.zeros((B, Hkv, g, Sq), jnp.float32),
        jnp.zeros((B, Hkv, g, Sq, dv), jnp.float32),
    )
    kc_t = jnp.moveaxis(kc, 1, 0)
    vc_t = jnp.moveaxis(vc, 1, 0)
    body_ckpt = jax.checkpoint(body)
    (m, l, acc), _ = jax.lax.scan(
        body_ckpt, init, (kc_t, vc_t, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B, Hkv, g, Sq, dv)
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, Hq, dv)
    return out.astype(q.dtype)


def attention(q, k, v, **kw):
    """TACC-dispatched attention (tpu -> Pallas flash kernel, cpu -> chunked)."""
    return tacc.dispatch("attention", q, k, v, **kw)


def dense_reference(q, k, v, *, kind="causal", window=0, q_offset=0,
                    k_offset=0, k_len=None, scale=None):
    """O(S^2)-memory oracle for tests."""
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = k_offset + jnp.arange(Sk)
    valid = _mask(q_pos, k_pos, kind, window)
    if k_len is not None:
        valid &= (k_pos < k_offset + k_len)[None, :]
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return jnp.moveaxis(out, 3, 1).reshape(B, Sq, Hq, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def cache_update(cache_k, cache_v, k_new, v_new, pos):
    """Insert (B, S_new, Hkv, hd) at offset ``pos`` (scalar)."""
    ck = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                      (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                      (0, pos, 0, 0))
    return ck, cv


def window_cache_update(cache_k, cache_v, k_new, v_new, pos):
    """Rolling cache of size W (SWA decode): slot = pos % W, single step."""
    W = cache_k.shape[1]
    slot = pos % W
    ck = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                      (0, slot, 0, 0))
    return ck, cv


def window_decode_attention(q, cache_k, cache_v, pos, window: int, **kw):
    """Decode vs a rolling window cache: positions are reconstructed mod W."""
    W = cache_k.shape[1]
    # slot i holds global position: largest p <= pos with p % W == i
    slots = jnp.arange(W)
    cur_slot = pos % W
    k_pos = pos - ((cur_slot - slots) % W)                 # (W,) global positions
    B, _, Hq, hd = q.shape
    _, _, Hkv, _ = cache_k.shape
    g = Hq // Hkv
    scale = hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, 1, Hkv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, cache_k.astype(jnp.float32))
    valid = (k_pos <= pos) & (k_pos > pos - window) & (k_pos >= 0)
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p, cache_v.astype(jnp.float32))
    return jnp.moveaxis(out, 3, 1).reshape(B, 1, Hq, hd).astype(q.dtype)
