"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel bodies execute in Python on CPU; TPU is the compile target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ssd_scan import ssd_scan_pallas

rng = np.random.RandomState(0)


@pytest.mark.parametrize("kind,window", [("causal", 0), ("bidir", 0),
                                         ("causal", 64)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,S,d", [(2, 4, 2, 256, 64), (1, 3, 1, 128, 32)])
def test_flash_attention_sweep(kind, window, dtype, B, Hq, Hkv, S, d):
    q = (rng.randn(B, Hq, S, d) * 0.5).astype(np.float32)
    k = (rng.randn(B, Hkv, S, d) * 0.5).astype(np.float32)
    v = (rng.randn(B, Hkv, S, d) * 0.5).astype(np.float32)
    qj, kj, vj = (jnp.asarray(t).astype(dtype) for t in (q, k, v))
    got = flash_attention_fwd(qj, kj, vj, kind=kind, window=window,
                              bq=128, bk=128, interpret=True)
    want = ref.attention(qj, kj, vj, kind=kind, window=window)
    atol = 3e-4 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_flash_attention_k_len():
    B, H, S, d = 1, 2, 256, 64
    q, k, v = (jnp.asarray(rng.randn(B, H, S, d), jnp.float32) for _ in range(3))
    got = flash_attention_fwd(q, k, v, kind="bidir", k_len=77, interpret=True)
    want = ref.attention(q, k, v, kind="bidir", k_len=77)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)


def _attention_grads(attn, q, k, v, w):
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _assert_grads_close(got, want, atol):
    (lg, gg), (lw, gw) = got, want
    np.testing.assert_allclose(float(lg), float(lw), rtol=1e-4)
    for a, b in zip(gg, gw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)


@pytest.mark.parametrize("kind,window", [("causal", 0), ("causal", 64),
                                         ("bidir", 0)])
def test_flash_attention_vjp_matches_reference(kind, window):
    """The TPU attention entry is differentiable: its custom VJP (Pallas
    forward, chunked_attention backward) gives the oracle's value and
    gradients, in model layout (B, S, H, d) with GQA and a padded S."""
    from repro.models.attention import dense_reference
    B, S, Hq, Hkv, d = 2, 200, 4, 2, 64
    q = jnp.asarray(rng.randn(B, S, Hq, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, d) * 0.5, jnp.float32)
    w = jnp.asarray(rng.randn(B, S, Hq, d), jnp.float32)
    kw = dict(kind=kind, window=window)
    got = jax.jit(lambda *a: _attention_grads(
        lambda q, k, v: ops.flash_attention(q, k, v, interpret=True, **kw),
        *a))(q, k, v, w)
    want = _attention_grads(lambda q, k, v: dense_reference(q, k, v, **kw),
                            q, k, v, w)
    _assert_grads_close(got, want, atol=1e-3)


@pytest.mark.parametrize("Hq,Hkv", [(4, 2), (3, 1)],
                         ids=["heads-split", "replicated"])
def test_flash_attention_in_partially_manual_region(mesh3, Hq, Hkv):
    """Inside a shard_map that leaves 'model' auto (the train step's
    layout), the kernel runs in a nested region where every axis is manual
    (Mosaic kernels cannot be auto-partitioned): heads split over 'model'
    where both head counts divide it, otherwise every 'model' rank runs the
    whole call.  Values and gradients match the oracle either way."""
    from jax.sharding import PartitionSpec as P
    from repro.core import compat
    from repro.models.attention import dense_reference
    B, S, d = 4, 128, 32
    q = jnp.asarray(rng.randn(B, S, Hq, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, d) * 0.5, jnp.float32)
    w = jnp.asarray(rng.randn(B, S, Hq, d), jnp.float32)
    dp = P(("pod", "data"))

    def body(q, k, v, w):
        val, grads = _attention_grads(
            lambda q, k, v: ops.flash_attention(q, k, v, interpret=True),
            q, k, v, w)
        return (jax.lax.psum(val, ("pod", "data")), *grads)

    sm = compat.shard_map(body, mesh=mesh3, in_specs=(dp,) * 4,
                          out_specs=(P(), dp, dp, dp),
                          axis_names={"pod", "data"})
    val, *grads = jax.jit(sm)(q, k, v, w)
    want = _attention_grads(dense_reference, q, k, v, w)
    _assert_grads_close((val, grads), want, atol=1e-3)


@pytest.mark.parametrize("G,M,K,N", [(4, 200, 96, 160), (1, 128, 128, 128),
                                     (8, 64, 300, 48)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_grouped_matmul_sweep(G, M, K, N, dtype):
    x = jnp.asarray(rng.randn(G, M, K), jnp.float32).astype(dtype)
    w = jnp.asarray(rng.randn(G, K, N) * 0.1, jnp.float32).astype(dtype)
    got = ops.grouped_matmul(x, w, interpret=True)
    want = ref.grouped_matmul(x, w)
    atol = 1e-3 if dtype == np.float32 else 1.5e-1
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol,
                               rtol=3e-2)


@pytest.mark.parametrize("B,H,nc,Q,P,N", [(2, 3, 4, 64, 32, 16),
                                          (1, 2, 8, 32, 16, 8)])
def test_ssd_scan_sweep(B, H, nc, Q, P, N):
    x = jnp.asarray(rng.randn(B, H, nc, Q, P) * 0.5, jnp.float32)
    dt = jnp.asarray(np.abs(rng.randn(B, H, nc, Q)) * 0.1, jnp.float32)
    A = jnp.asarray(-np.abs(rng.randn(H)), jnp.float32)
    a_cum = jnp.cumsum(dt * A[None, :, None, None], axis=3)
    Bi = jnp.asarray(rng.randn(B, H, nc, Q, N) * 0.5, jnp.float32)
    Ci = jnp.asarray(rng.randn(B, H, nc, Q, N) * 0.5, jnp.float32)
    got = ssd_scan_pallas(x, dt, a_cum, Bi, Ci, interpret=True)
    want = ref.ssd_scan(x, dt, a_cum, Bi, Ci)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


def test_ssd_chunked_matches_sequential_recurrence():
    """The chunked SSD algorithm == the plain O(S) recurrence."""
    from repro.models import ssm as ssm_mod
    B, S, H, P, N = 2, 96, 4, 16, 8
    x = jnp.asarray(rng.randn(B, S, H, P) * 0.5, jnp.float32)
    dt = jnp.asarray(np.abs(rng.randn(B, S, H)) * 0.1, jnp.float32)
    A = jnp.asarray(-np.abs(rng.randn(H)), jnp.float32)
    Bi = jnp.asarray(rng.randn(B, S, 1, N) * 0.5, jnp.float32)   # G=1 groups
    Ci = jnp.asarray(rng.randn(B, S, 1, N) * 0.5, jnp.float32)
    D = jnp.asarray(rng.randn(H), jnp.float32)
    y_c, s_c = ssm_mod.ssd_scan(x, dt, A, Bi, Ci, D, chunk=32)
    y_s, s_s = ssm_mod.ssd_reference(x, dt, A, Bi, Ci, D)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s), atol=2e-3)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_s), atol=2e-3)


@pytest.mark.parametrize("n,dtype_in", [(1000, jnp.bfloat16), (4096, jnp.float32),
                                        (257, jnp.bfloat16)])
def test_collective_reduce_sweep(n, dtype_in):
    a = jnp.asarray(rng.randn(n), jnp.float32)
    b = jnp.asarray(rng.randn(n), jnp.float32).astype(dtype_in)
    got = ops.collective_reduce(a, b, interpret=True)
    want = ref.collective_reduce(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("shape,block", [
    ((300, 300), (256, 256)),    # ragged in both dims
    ((7, 130), (8, 128)),        # smaller than one block in M, ragged in L
    ((513, 1), (256, 256)),      # ragged chunk tail from an odd channel split
])
def test_collective_reduce_ragged_shapes(shape, block):
    """Regression: non-divisible (M, L) used to hard-assert; the kernel must
    pad-and-slice instead (ragged chunk tails from the multi-channel payload
    splits, DESIGN.md §10)."""
    from repro.kernels.collective_reduce import collective_reduce as cr
    a = jnp.asarray(rng.randn(*shape), jnp.float32)
    b = jnp.asarray(rng.randn(*shape), jnp.float32).astype(jnp.bfloat16)
    got = cr(a, b, block=block, interpret=True)
    want = ref.collective_reduce(a, b)
    assert got.shape == shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_attention_chunked_matches_dense():
    """The model's chunked online-softmax path == dense oracle."""
    from repro.models.attention import chunked_attention, dense_reference
    B, S, Hq, Hkv, d = 2, 128, 4, 2, 32
    q = jnp.asarray(rng.randn(B, S, Hq, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, d) * 0.5, jnp.float32)
    for kind, w in [("causal", 0), ("bidir", 0), ("causal", 17)]:
        got = chunked_attention(q, k, v, kind=kind, window=w, chunk=48)
        want = dense_reference(q, k, v, kind=kind, window=w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)


def test_window_decode_attention_matches_full():
    """Rolling-window cache decode == full-cache SWA decode."""
    from repro.models.attention import (chunked_attention, window_cache_update,
                                        window_decode_attention)
    B, Hkv, Hq, d, W = 1, 2, 4, 16, 8
    S = 20
    k_all = jnp.asarray(rng.randn(B, S, Hkv, d) * 0.5, jnp.float32)
    v_all = jnp.asarray(rng.randn(B, S, Hkv, d) * 0.5, jnp.float32)
    # build the rolling cache by replaying all steps
    ck = jnp.zeros((B, W, Hkv, d))
    cv = jnp.zeros((B, W, Hkv, d))
    for t in range(S):
        ck, cv = window_cache_update(ck, cv, k_all[:, t:t+1], v_all[:, t:t+1], t)
    q = jnp.asarray(rng.randn(B, 1, Hq, d) * 0.5, jnp.float32)
    got = window_decode_attention(q, ck, cv, S - 1, W)
    want = chunked_attention(q, k_all, v_all, kind="causal", window=W,
                             q_offset=S - 1, chunk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)


@pytest.mark.parametrize("codec,denom", [("int8", 254.0), ("fp8", 16.0)])
@pytest.mark.parametrize("n", [512, 1000, 4096, 257])
def test_wire_quant_roundtrip_error_bound(codec, denom, n):
    """Per-chunk absmax scaling bounds the round-trip error at half a code
    step: absmax/254 for int8, absmax/16 for the e4m3 software codec
    (DESIGN.md §17 wire format)."""
    from repro.kernels import quant
    x = jnp.asarray(rng.randn(n) * 3.0, jnp.float32)
    y = jax.jit(lambda v: quant.compress(v, codec=codec))(x)
    pad = (-n) % quant.DEFAULT_CHUNK
    xc = np.pad(np.asarray(x), (0, pad)).reshape(-1, quant.DEFAULT_CHUNK)
    ec = np.pad(np.abs(np.asarray(y - x)), (0, pad)).reshape(xc.shape)
    bound = np.abs(xc).max(axis=1) / denom
    assert (ec.max(axis=1) <= bound + 1e-7).all(), (ec.max(axis=1), bound)


@pytest.mark.parametrize("codec", ["int8", "fp8"])
def test_wire_quant_platform_equivalence_under_jit(codec):
    """cpu reference and interpret-mode Pallas kernels produce bit-identical
    codes, scales and accumulates under jit — the only context the ring
    dispatches them in (DESIGN.md §17)."""
    from repro.core import tacc
    from repro.kernels import quant
    x = jnp.asarray(rng.randn(1300) * 2.0, jnp.float32)
    acc = jnp.asarray(rng.randn(1300), jnp.float32)
    outs = {}
    for plat in ("cpu", "interpret"):
        tacc.set_platform(plat)
        try:
            codes, scales = jax.jit(
                lambda v: quant.quantize(v, codec=codec))(x)
            got = jax.jit(lambda a, c, s: quant.dequantize_accumulate(
                a, c, s, codec=codec))(acc, codes, scales)
        finally:
            tacc.set_platform_auto()
        outs[plat] = (np.asarray(codes), np.asarray(scales), np.asarray(got))
    for a, b in zip(outs["cpu"], outs["interpret"]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("shape", [
    (300, 300),       # ragged in both dims
    (7, 130),         # payload smaller than one chunk
    (513, 1),         # ragged chunk tail from an odd channel split
])
def test_wire_quant_ragged_shapes(shape):
    """Regression: non-divisible (M, L) payloads pad-and-slice through the
    chunked quantizer — codes keep the payload shape, the accumulate never
    touches the zero padding (ragged tails from the multi-channel splits,
    DESIGN.md §17)."""
    from repro.kernels import quant
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    acc = jnp.asarray(rng.randn(*shape), jnp.float32)
    codes, scales = jax.jit(quant.quantize)(x)
    assert codes.shape == shape and codes.dtype == jnp.int8
    got = jax.jit(quant.dequantize_accumulate)(acc, codes, scales)
    assert got.shape == shape
    pad = (-x.size) % quant.DEFAULT_CHUNK
    xc = np.pad(np.asarray(x).reshape(-1), (0, pad)).reshape(
        -1, quant.DEFAULT_CHUNK)
    absmax = np.abs(xc).max(1)              # f32 throughout, like the codec
    np.testing.assert_allclose(             # absmax sidecar (1 ulp: XLA may
        np.asarray(scales).reshape(-1),     # fuse the /127 as a reciprocal)
        np.where(absmax == 0, np.float32(1.0), absmax / np.float32(127.0)),
        rtol=1e-6)
    err = np.abs(np.asarray(got) - (np.asarray(acc) + np.asarray(x)))
    ec = np.pad(err.reshape(-1), (0, pad)).reshape(xc.shape)
    bound = np.abs(xc).max(axis=1) / 254.0
    assert (ec.max(axis=1) <= bound + 1e-7).all()
