"""Pure-jnp oracles for every Pallas kernel (kernel-layout signatures)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention(q, k, v, *, kind="causal", window=0, k_len=None, scale=None):
    """q (B,Hq,S,d), k/v (B,Hkv,Sk,d) -> (B,Hq,S,d).  Dense softmax oracle."""
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else d ** -0.5
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, Sq, d) * scale
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, k.astype(jnp.float32))
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    valid = jnp.ones((Sq, Sk), bool)
    if kind == "causal":
        valid &= q_pos >= k_pos
    if window:
        valid &= (q_pos - k_pos) < window
    if k_len is not None:
        valid &= (k_pos < k_len)
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, Sq, v.shape[-1]).astype(q.dtype)


def grouped_matmul(x, w):
    """x (G,M,K) @ w (G,K,N) -> (G,M,N), f32 accumulation."""
    out = jnp.einsum("gmk,gkn->gmn", x.astype(jnp.float32),
                     w.astype(jnp.float32))
    return out.astype(x.dtype)


def ragged_grouped_matmul(x, w, group_sizes):
    """x (R,K) @ w[g] (G,K,N) for the rows of each group, the groups laid
    out in order from row 0 -> (R,N); rows past the last group are zero."""
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32).astype(x.dtype)


def ssd_scan(x, dt, a_cum, B_in, C_in):
    """Kernel-layout SSD oracle.  x (B,H,nc,Q,P), dt/a_cum (B,H,nc,Q),
    B_in/C_in (B,H,nc,Q,N) -> (B,H,nc,Q,P)."""
    Bb, H, nc, Q, P = x.shape
    N = B_in.shape[-1]
    a = a_cum.astype(jnp.float32)
    ii = jnp.arange(Q)[:, None]
    jj = jnp.arange(Q)[None, :]
    causal = ii >= jj
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    def chunk(carry, idx):
        s = carry                                     # (B,H,N,P)
        ac = a[:, :, idx]                             # (B,H,Q)
        Bc = B_in[:, :, idx].astype(jnp.float32)
        Cc = C_in[:, :, idx].astype(jnp.float32)
        xc = xdt[:, :, idx]
        diff = ac[:, :, :, None] - ac[:, :, None, :]
        diff = jnp.where(causal[None, None], diff, 0.0)   # mask pre-exp
        L = jnp.where(causal[None, None], jnp.exp(diff), 0.0)
        scores = jnp.einsum("bhin,bhjn->bhij", Cc, Bc)
        y = jnp.einsum("bhij,bhjp->bhip", scores * L, xc)
        y += jnp.einsum("bhin,bhnp->bhip", Cc, s) * jnp.exp(ac)[..., None]
        decay_end = jnp.exp(ac[:, :, -1:] - ac)       # (B,H,Q)
        s_new = jnp.einsum("bhjn,bhjp->bhnp", Bc * decay_end[..., None], xc)
        s = jnp.exp(ac[:, :, -1])[:, :, None, None] * s + s_new
        return s, y

    s0 = jnp.zeros((Bb, H, N, P), jnp.float32)
    _, ys = jax.lax.scan(chunk, s0, jnp.arange(nc))
    return jnp.moveaxis(ys, 0, 2).astype(x.dtype)


def collective_reduce(acc, incoming):
    return (acc.astype(jnp.float32) + incoming.astype(jnp.float32)).astype(acc.dtype)
