"""Flash attention forward and backward kernels (Pallas TPU).

Forward (``_flash_kernel``): online-softmax attention with explicit
BlockSpec VMEM tiling:

  grid = (B, Hq, Sq/bq, Sk/bk), dimension_semantics = (parallel, parallel,
  parallel, arbitrary) — the innermost k-block axis is sequential, carrying
  (m, l, acc) in VMEM scratch; on the last k-step it writes the output block
  and the rows' logsumexp ``lse = m + log(l)`` (f32, ``(B, Hq, 1, Sq)``: one
  lane-dense row per head).  GQA is handled in the k/v index_map (kv head =
  q head // group).  v (and so o, dO and dV) may be narrower than q and k
  (multi-head latent attention: q, k 192 wide, v 128).

Backward (FlashAttention-2): with ``D = rowsum(dO * O)``, every block pair
recomputes ``P = exp(s - lse)`` from q and k (``s = q k^T * scale``) and
``dS = P * (dO v^T - D)``.  Two kernels:

- ``_flash_bwd_dkv_kernel``: grid (B, Hkv, Sk/bk, g, Sq/bq); the last two
  axes are sequential over the q blocks of every q head in the kv head's
  GQA group, accumulating ``dV += P^T dO`` and ``dK += dS^T q * scale`` in
  f32 VMEM scratch.  It works on the transposed block (kv rows, q columns)
  so that ``lse`` and ``D`` broadcast as rows.
- ``_flash_bwd_dq_kernel``: grid (B, Hq, Sq/bq, Sk/bk), sequential over kv
  blocks, accumulating ``dQ += dS k * scale``.

A block pair wholly masked (above the causal diagonal, outside the window,
past ``k_len``) is neither fetched nor computed: the index_maps clamp the
sequential axis to the live range, so the block index repeats and no DMA is
issued, and the compute is under ``pl.when``.  Iota masks are built only on
the blocks the diagonal, the window edge or ``k_len`` crosses.  The
backward's block sizes come from the shapes (:func:`bwd_blocks`).

Precision: MXU operands in the inputs' dtype (P and dS are cast to it),
f32 accumulation; softmax statistics, D and dS's elementwise math in f32.

MXU alignment: bq/bk default 128 (q is padded by ops.py when Sq < bq);
head_dim should be a multiple of 128 for full MXU utilization — smaller
head dims still compile but underfill the systolic array.

Masking supports causal, bidirectional and sliding-window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import compat

NEG_INF = -1e30
LANES = 128
# The kernels' names in the compiled program and the device trace, where the
# benchmark finds them (``bench/metrics/flash_fwd_roofline.py``,
# ``bench/metrics/attention_bwd_kernel_share.py``); kept fixed.
KERNEL_NAME = "_flash_kernel"
BWD_DKV_KERNEL_NAME = "_flash_bwd_dkv_kernel"
BWD_DQ_KERNEL_NAME = "_flash_bwd_dq_kernel"
# Backward block sizes, largest first: the largest that divides the padded
# length is taken.  512 beat 256 and 1024 for both kernels at seq 2048, head
# 64 on a TPU v5e (``benchmarks/attention_bwd_sweep.py``).
BWD_BLOCKS = (512, 256, 128)


def _col_to_row(x):
    """(n, 1) -> (1, n) through a lane-aligned 2-D transpose."""
    return jnp.transpose(jnp.broadcast_to(x, (x.shape[0], LANES)))[:1]


def _row_to_col(x):
    """(1, n) -> (n, 1)."""
    return jnp.transpose(jnp.broadcast_to(x, (LANES, x.shape[1])))[:, :1]


def _valid(q_pos, k_pos, *, kind, window, k_len):
    """Which (q, kv) positions attend: kv inside ``k_len``, and with
    ``causal`` not after q, and with a window less than ``window`` behind."""
    valid = k_pos < k_len
    if kind == "causal":
        valid &= q_pos >= k_pos
    if window:
        valid &= (q_pos - k_pos) < window
    return valid


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, kind: str, window: int, bq: int, bk: int,
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
    valid = _valid(q_pos, k_pos, kind=kind, window=window, k_len=k_len)

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
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _col_to_row(m_scr[...] + jnp.log(l))


def flash_attention_fwd(q, k, v, *, kind: str = "causal", window: int = 0,
                        k_len: int | None = None, scale: float | None = None,
                        bq: int = 128, bk: int = 128,
                        interpret: bool = False):
    """q, k: (B, Hq, Sq, d), (B, Hkv, Sk, d);  v: (B, Hkv, Sk, dv) ->
    (o (B, Hq, Sq, dv), lse (B, Hq, 1, Sq) f32).

    Shapes must be block-aligned (ops.py pads); GQA via Hq = g * Hkv.
    """
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    dv = v.shape[-1]
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
            pl.BlockSpec((1, 1, bk, dv), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, Sq, dv), q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, 1, Sq), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def bwd_blocks(n: int) -> int:
    """The backward's block along an axis of padded length ``n`` (a multiple
    of 128): the largest of ``BWD_BLOCKS`` that divides it."""
    return next((b for b in BWD_BLOCKS if n % b == 0), n)


def _pair(qi, kj, *, kind, window, k_len, bq, bk):
    """(live, whole) for the block pair (q block qi, kv block kj): some /
    every entry of it is unmasked."""
    q_lo, k_lo = qi * bq, kj * bk
    q_hi, k_hi = q_lo + bq - 1, k_lo + bk - 1
    live, whole = k_lo < k_len, k_hi < k_len
    if kind == "causal":
        live &= q_hi >= k_lo
        whole &= q_lo >= k_hi
    if window:
        live &= q_lo - k_hi < window
        whole &= q_hi - k_lo < window
    return live, whole


def _q_range(kj, *, kind, window, bq, bk, n_q):
    """First and last q block live against kv block ``kj``."""
    lo, hi = 0, n_q - 1
    if kind == "causal":
        lo = jnp.minimum(kj * bk // bq, hi)
    if window:
        hi = jnp.minimum((window + kj * bk + bk - 2) // bq, hi)
    return lo, hi


def _k_range(qi, *, kind, window, k_len, bq, bk, n_k):
    """First and last kv block live against q block ``qi``."""
    hi = min(n_k - 1, (k_len - 1) // bk)
    if kind == "causal":
        hi = jnp.minimum((qi * bq + bq - 1) // bk, hi)
    lo = 0
    if window:   # ceil((q_lo - window - bk + 2) / bk), at least 0
        lo = jnp.maximum(-((window + bk - 2 - qi * bq) // bk), 0)
    return jnp.minimum(lo, hi), hi


def _when_live(live, whole, step):
    """Run ``step(masked)`` on a live block pair, with the iota mask only
    where the pair is not wholly unmasked."""
    pl.when(live & whole)(lambda: step(False))
    pl.when(live & jnp.logical_not(whole))(lambda: step(True))


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                          kind: str, window: int, bq: int, bk: int,
                          k_len: int):
    kj = pl.program_id(2)
    gi, qi = pl.program_id(3), pl.program_id(4)
    last = (gi == pl.num_programs(3) - 1) & (qi == pl.num_programs(4) - 1)

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        # transposed block: kv positions along rows, q positions along lanes
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        p = jnp.exp(s * scale - lse_ref[0, 0])                # (bk, bq)
        if masked:
            k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            p = jnp.where(_valid(q_pos, k_pos, kind=kind, window=window,
                                 k_len=k_len), p, 0.0)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, 0])
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _when_live(*_pair(qi, kj, kind=kind, window=window, k_len=k_len, bq=bq,
                      bk=bk), step)

    @pl.when(last)
    def _finish():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                         dq_scr, lse_scr, di_scr, *, scale: float, kind: str,
                         window: int, bq: int, bk: int, k_len: int):
    qi, kj = pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        lse_scr[...] = _row_to_col(lse_ref[0, 0])
        di_scr[...] = _row_to_col(di_ref[0, 0])

    def step(masked):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        p = jnp.exp(s * scale - lse_scr[...])                 # (bq, bk)
        if masked:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            p = jnp.where(_valid(q_pos, k_pos, kind=kind, window=window,
                                 k_len=k_len), p, 0.0)
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - di_scr[...])
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _when_live(*_pair(qi, kj, kind=kind, window=window, k_len=k_len, bq=bq,
                      bk=bk), step)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_setup(q, k, scale, k_len, bq, bk):
    B, Hq, Sq, d = q.shape
    Sk = k.shape[2]
    bq = bq or bwd_blocks(Sq)
    bk = bk or bwd_blocks(Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    return dict(scale=scale if scale is not None else d ** -0.5, bq=bq, bk=bk,
                k_len=Sk if k_len is None else k_len)


def flash_bwd_dkv(q, k, v, do, lse, di, *, kind: str = "causal",
                  window: int = 0, k_len: int | None = None,
                  scale: float | None = None, bq: int | None = None,
                  bk: int | None = None, interpret: bool = False):
    """(dk, dv) by ``_flash_bwd_dkv_kernel``: kv blocks outer, (q head of
    the GQA group, q block) sequential.  Operands as
    :func:`flash_attention_bwd`'s, with ``di = rowsum(do * o)`` shaped as
    ``lse``."""
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    dv = v.shape[-1]
    g = Hq // Hkv
    st = _bwd_setup(q, k, scale, k_len, bq, bk)
    bq, bk = st["bq"], st["bk"]
    n_q = Sq // bq

    def q_blk(j, i):
        return jnp.clip(i, *_q_range(j, kind=kind, window=window, bq=bq,
                                     bk=bk, n_q=n_q))

    from jax.experimental.pallas import tpu as pltpu
    def q_map(b, h, j, gi, i):
        return (b, h * g + gi, q_blk(j, i), 0)

    def kv_map(b, h, j, gi, i):
        return (b, h, j, 0)

    q_spec = pl.BlockSpec((1, 1, bq, d), q_map)
    do_spec = pl.BlockSpec((1, 1, bq, dv), q_map)
    row_spec = pl.BlockSpec((1, 1, 1, bq), lambda b, h, j, gi, i:
                            (b, h * g + gi, 0, q_blk(j, i)))
    k_spec = pl.BlockSpec((1, 1, bk, d), kv_map)
    v_spec = pl.BlockSpec((1, 1, bk, dv), kv_map)
    return pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, kind=kind, window=window,
                          **st),
        grid=(B, Hkv, Sk // bk, g, n_q),
        in_specs=[q_spec, k_spec, v_spec, do_spec, row_spec, row_spec],
        out_specs=[k_spec, v_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
        name=BWD_DKV_KERNEL_NAME,
    )(q, k, v, do, lse, di)


def flash_bwd_dq(q, k, v, do, lse, di, *, kind: str = "causal",
                 window: int = 0, k_len: int | None = None,
                 scale: float | None = None, bq: int | None = None,
                 bk: int | None = None, interpret: bool = False):
    """dq by ``_flash_bwd_dq_kernel``: q blocks outer, kv blocks
    sequential.  Operands as :func:`flash_bwd_dkv`'s."""
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    dv = v.shape[-1]
    g = Hq // Hkv
    st = _bwd_setup(q, k, scale, k_len, bq, bk)
    bq, bk = st["bq"], st["bk"]
    n_k = Sk // bk

    def k_blk(i, j):
        return jnp.clip(j, *_k_range(i, kind=kind, window=window,
                                     k_len=st["k_len"], bq=bq, bk=bk, n_k=n_k))

    from jax.experimental.pallas import tpu as pltpu
    def q_map(b, h, i, j):
        return (b, h, i, 0)

    def kv_map(b, h, i, j):
        return (b, h // g, k_blk(i, j), 0)

    q_spec = pl.BlockSpec((1, 1, bq, d), q_map)
    row_spec = pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i))
    return pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, kind=kind, window=window,
                          **st),
        grid=(B, Hq, Sq // bq, n_k),
        in_specs=[q_spec, pl.BlockSpec((1, 1, bk, d), kv_map),
                  pl.BlockSpec((1, 1, bk, dv), kv_map),
                  pl.BlockSpec((1, 1, bq, dv), q_map), row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=BWD_DQ_KERNEL_NAME,
    )(q, k, v, do, lse, di)


def flash_attention_bwd(q, k, v, o, lse, do, **kw):
    """Gradients (dq, dk, dv) of the forward's output ``o`` given its
    cotangent ``do``, from the forward's ``lse``.

    q: (B, Hq, Sq, d);  k: (B, Hkv, Sk, d);  v: (B, Hkv, Sk, dv);  o, do:
    (B, Hq, Sq, dv);  lse: (B, Hq, 1, Sq).
    Block-aligned as the forward's.  Keywords as :func:`flash_bwd_dkv`'s;
    bq/bk default to :func:`bwd_blocks` of the lengths.
    """
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)[:, :, None, :]
    dk, dv = flash_bwd_dkv(q, k, v, do, lse, di, **kw)
    return flash_bwd_dq(q, k, v, do, lse, di, **kw), dk, dv
