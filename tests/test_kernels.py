"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel bodies execute in Python on CPU; TPU is the compile target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_bwd, flash_attention_fwd
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
    got, _ = flash_attention_fwd(qj, kj, vj, kind=kind, window=window,
                                 bq=128, bk=128, interpret=True)
    want = ref.attention(qj, kj, vj, kind=kind, window=window)
    atol = 3e-4 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_flash_attention_k_len():
    B, H, S, d = 1, 2, 256, 64
    q, k, v = (jnp.asarray(rng.randn(B, H, S, d), jnp.float32) for _ in range(3))
    got, _ = flash_attention_fwd(q, k, v, kind="bidir", k_len=77, interpret=True)
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


def _bmodel(x):
    """Kernel layout (B, H, S, d) <-> model layout (B, S, H, d)."""
    return jnp.swapaxes(x, 1, 2)


def _assert_rel_close(got, want, rtol):
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=rtol * np.abs(b).max())


# gradient tolerance relative to each gradient's largest entry: bf16 rounds
# the operands, P and dS (eps 2^-8) on both sides of the comparison
BWD_RTOL = {np.float32: 1e-4, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("kind,window", [("causal", 0), ("causal", 64),
                                         ("bidir", 0)])
@pytest.mark.parametrize("Hq,Hkv", [(4, 2), (3, 1)])
@pytest.mark.parametrize("Sq,Sk", [(200, 200), (128, 256)],
                         ids=["padded", "cross"])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_bwd_kernels(kind, window, Hq, Hkv, Sq, Sk, dtype):
    """The dK/dV and dQ kernels' gradients, through the TPU attention entry
    (model layout, S padded to the blocks), match ``jax.vjp`` of the dense
    oracle on the same inputs."""
    from repro.models.attention import dense_reference
    B, d = 2, 32
    q = jnp.asarray(rng.randn(B, Sq, Hq, d) * 0.5, jnp.float32).astype(dtype)
    k = jnp.asarray(rng.randn(B, Sk, Hkv, d) * 0.5, jnp.float32).astype(dtype)
    v = jnp.asarray(rng.randn(B, Sk, Hkv, d) * 0.5, jnp.float32).astype(dtype)
    do = jnp.asarray(rng.randn(B, Sq, Hq, d), jnp.float32).astype(dtype)
    kw = dict(kind=kind, window=window)
    _, vjp = jax.vjp(lambda q, k, v: ops.flash_attention(
        q, k, v, interpret=True, **kw), q, k, v)
    got = jax.jit(vjp)(do)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    _, vjp_ref = jax.vjp(lambda q, k, v: dense_reference(q, k, v, **kw), *f32[:3])
    assert all(g.dtype == x.dtype for g, x in zip(got, (q, k, v)))
    _assert_rel_close(got, vjp_ref(f32[3]), BWD_RTOL[dtype])


@pytest.mark.parametrize("kind,window,S,k_len", [
    ("causal", 0, 256, None),       # (q block 0, kv block 1): above the diagonal
    ("causal", 64, 384, None),      # (q block 2, kv block 0): outside the window
    ("bidir", 0, 256, 77),          # kv block 1: past k_len
], ids=["diagonal", "window", "k_len"])
def test_flash_attention_bwd_wholly_masked_blocks(kind, window, S, k_len):
    """At 128-wide blocks some block pairs are wholly masked (neither
    fetched nor computed); the gradients still match the oracle's, with
    zeros for keys past ``k_len``."""
    from repro.models.attention import dense_reference
    B, Hq, Hkv, d = 1, 2, 1, 64
    q, do = (jnp.asarray(rng.randn(B, Hq, S, d) * 0.5, jnp.float32) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(B, Hkv, S, d) * 0.5, jnp.float32) for _ in range(2))
    kw = dict(kind=kind, window=window, k_len=k_len)
    o, lse = flash_attention_fwd(q, k, v, interpret=True, **kw)
    got = flash_attention_bwd(q, k, v, o, lse, do, bq=128, bk=128,
                              interpret=True, **kw)
    _, vjp = jax.vjp(lambda q, k, v: _bmodel(dense_reference(
        _bmodel(q), _bmodel(k), _bmodel(v), **kw)), q, k, v)
    want = vjp(do)
    _assert_rel_close(got, want, BWD_RTOL[np.float32])
    if k_len is not None:
        assert not np.asarray(got[1][:, :, k_len:]).any()


@pytest.mark.parametrize("kind,window,k_len", [
    ("causal", 0, 1024), ("causal", 64, 1024), ("causal", 700, 1024),
    ("bidir", 0, 1024), ("bidir", 0, 600), ("causal", 300, 777)])
@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 128), (128, 512),
                                   (512, 256)])
def test_flash_attention_bwd_live_ranges(kind, window, k_len, bq, bk):
    """The index maps clamp the sequential axis to the live range: every
    live block pair lies inside it (so it is fetched), and with a causal
    diagonal or a window the dead ones outside it (so they are not)."""
    from repro.kernels import flash_attention as fa
    S = 1024
    n_q, n_k = S // bq, S // bk
    st = dict(kind=kind, window=window, bq=bq, bk=bk)
    for i in range(n_q):
        for j in range(n_k):
            live = bool(fa._pair(i, j, k_len=k_len, **st)[0])
            q_lo, q_hi = (int(x) for x in fa._q_range(j, n_q=n_q, **st))
            k_lo, k_hi = (int(x) for x in fa._k_range(i, k_len=k_len, n_k=n_k, **st))
            if live:
                assert q_lo <= i <= q_hi and k_lo <= j <= k_hi, (i, j)
            elif kind == "causal" and j * bk < k_len:
                assert not q_lo <= i <= q_hi and not k_lo <= j <= k_hi, (i, j)


@pytest.mark.parametrize("kind,window,k_len", [
    ("causal", 0, None), ("bidir", 0, None), ("causal", 300, None),
    ("bidir", 0, 700), ("causal", 0, 600)],
    ids=["causal", "bidir", "window", "k_len", "causal-k_len"])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_fwd_at_chosen_blocks(kind, window, k_len, dtype):
    """The forward at the blocks the shapes choose (``fwd_blocks``), with
    GQA and v narrower than q and k, matches the dense oracle; f32 inputs
    at the f32 tolerance the 128-wide blocks are held to."""
    from repro.kernels import flash_attention as fa
    B, Hq, Hkv, S, d, dv = 1, 4, 2, 1024, 64, 32
    assert fa.fwd_blocks(S, S, d, dv, jnp.dtype(dtype).itemsize) == (S, S)
    q = jnp.asarray(rng.randn(B, Hq, S, d) * 0.5, jnp.float32).astype(dtype)
    k = jnp.asarray(rng.randn(B, Hkv, S, d) * 0.5, jnp.float32).astype(dtype)
    v = jnp.asarray(rng.randn(B, Hkv, S, dv) * 0.5, jnp.float32).astype(dtype)
    kw = dict(kind=kind, window=window, k_len=k_len)
    got, lse = flash_attention_fwd(q, k, v, interpret=True, **kw)
    want = ref.attention(q, k, v, **kw)
    assert got.shape == (B, Hq, S, dv) and lse.shape == (B, Hq, 1, S)
    atol = 3e-4 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("n,block", [(128, 128), (256, 256), (640, 128),
                                     (768, 256), (1152, 128), (1536, 512),
                                     (2048, 1024), (8192, 1024)])
def test_flash_attention_fwd_blocks_divide_the_padded_length(n, block):
    """The forward's block divides the 128-padded length, and lengths 512
    does not divide take a smaller divisor, not more padding."""
    from repro.kernels import flash_attention as fa
    assert fa.fwd_blocks(n, n, 64, 64, 2) == (block, block)
    assert fa.fwd_blocks(n, 2048, 192, 128, 2) == (block, 1024)


@pytest.mark.parametrize("d,dv,itemsize,blocks", [
    (64, 64, 2, (1024, 1024)), (192, 128, 2, (1024, 1024)),
    (192, 128, 4, (1024, 1024)), (256, 256, 2, (1024, 1024)),
    (256, 256, 4, (512, 512)), (512, 512, 2, (512, 512))])
def test_flash_attention_fwd_blocks_fit_scoped_vmem(d, dv, itemsize, blocks):
    """Wide heads and f32 inputs step the forward's blocks down until they
    fit the scoped VMEM (``tests/test_chip_compile.py`` compiles the ones
    that step down for a v5e)."""
    from repro.kernels import flash_attention as fa
    assert fa.fwd_blocks(2048, 2048, d, dv, itemsize) == blocks
    assert fa._fwd_vmem(*blocks, d, dv, itemsize) <= fa.FWD_VMEM_BYTES


@pytest.mark.parametrize("S", [600, 1100], ids=["640", "1152"])
def test_flash_attention_padded_residuals_stay_128_aligned(S):
    """Through the model-layout entry at lengths 512 does not divide, the
    backward's residuals are padded to a multiple of 128 only, and the
    forward's value and gradients match the oracle's."""
    from repro.models.attention import dense_reference
    B, Hq, Hkv, d = 1, 2, 1, 32
    q = jnp.asarray(rng.randn(B, S, Hq, d) * 0.5, jnp.float32)
    k, v = (jnp.asarray(rng.randn(B, S, Hkv, d) * 0.5, jnp.float32)
            for _ in range(2))
    w = jnp.asarray(rng.randn(B, S, Hq, d), jnp.float32)
    cfg = ops._FlashCfg("causal", 0, S, None, None, None, True, S)
    _, res = ops._flash_manual(q, k, v, cfg, residuals=True)
    padded = -(-S // 128) * 128
    assert [r.shape[2] for r in res[:4]] == [padded] * 4
    assert res[4].shape == (B, Hq, 1, padded)
    got = jax.jit(lambda *a: _attention_grads(
        lambda q, k, v: ops.flash_attention(q, k, v, interpret=True),
        *a))(q, k, v, w)
    want = _attention_grads(dense_reference, q, k, v, w)
    _assert_grads_close(got, want, atol=1e-3)


@pytest.mark.parametrize("kind,window,k_len", [
    ("causal", 0, 2048), ("causal", 300, 2048), ("causal", 700, 1500),
    ("bidir", 0, 2048), ("bidir", 0, 900)])
@pytest.mark.parametrize("bq,bk", [(512, 512), (256, 512), (512, 128),
                                   (1024, 256)])
def test_flash_attention_fwd_clamped_index_map(kind, window, k_len, bq, bk):
    """The forward's kv index map (``_k_blk``) keeps every live step's own
    block and sends every dead step to a live block of the same q block,
    so a dead step issues no fetch of its own."""
    from repro.kernels import flash_attention as fa
    S = 2048
    n_q, n_k = S // bq, S // bk
    st = dict(kind=kind, window=window, k_len=k_len, bq=bq, bk=bk)
    for i in range(n_q):
        for j in range(n_k):
            jj = int(fa._k_blk(i, j, n_k=n_k, **st))
            assert bool(fa._pair(i, jj, **st)[0]), (i, j, jj)
            if bool(fa._pair(i, j, **st)[0]):
                assert jj == j, (i, j, jj)


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


def _ragged_rows(sizes, K, dtype, spare_tiles=1):
    """x (R, K) holding groups of ``sizes`` rows, each starting on a
    multiple of ROW_TILE, zero elsewhere; and the padded group sizes."""
    from repro.kernels.grouped_matmul import ROW_TILE
    padded = -(-np.asarray(sizes) // ROW_TILE) * ROW_TILE
    x = np.zeros((int(padded.sum()) + spare_tiles * ROW_TILE, K), np.float32)
    start = 0
    for n, p in zip(sizes, padded):
        x[start:start + n] = rng.randn(n, K)
        start += p
    return jnp.asarray(x).astype(dtype), jnp.asarray(padded, jnp.int32)


@pytest.mark.parametrize("sizes", [(130, 0, 5, 256), (0, 0, 0), (128, 1, 300)],
                         ids=["empty-group", "all-empty", "ragged"])
@pytest.mark.parametrize("K,N", [(256, 384), (128, 128)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_ragged_grouped_matmul_kernels(sizes, K, N, dtype):
    """``_gmm_ragged_kernel`` (forward, and dX with w transposed) and
    ``_tgmm_ragged_kernel`` (dW) in interpret mode, through the custom VJP,
    against ``jax.lax.ragged_dot`` and its VJP on the same padded layout:
    rows past the groups and a group with no rows come out zero."""
    x, gs = _ragged_rows(sizes, K, dtype)
    G = len(sizes)
    w = jnp.asarray(rng.randn(G, K, N) * 0.1, jnp.float32).astype(dtype)
    dy = jnp.asarray(rng.randn(x.shape[0], N), jnp.float32).astype(dtype)
    got, vjp = jax.vjp(lambda x, w: ops.ragged_grouped_matmul(x, w, gs, interpret=True), x, w)
    want, vjp_ref = jax.vjp(lambda x, w: ref.ragged_grouped_matmul(x, w, gs), x, w)
    # relative to each result's largest entry: both sides accumulate in f32
    # from the same operands; bf16 results round to 2**-8
    atol = 1e-4 if dtype == np.float32 else 1e-2
    for a, b in zip((got, *vjp(dy)), (want, *vjp_ref(dy))):
        assert a.dtype == b.dtype
        scale = max(float(jnp.max(jnp.abs(b.astype(jnp.float32)))), 1.0)
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   atol=atol * scale)
    ends = np.cumsum(np.asarray(gs))
    assert not np.asarray(got[ends[-1]:], np.float32).any()
    dw = vjp(dy)[1]
    for g, n in enumerate(sizes):
        if n == 0:
            assert not np.asarray(dw[g], np.float32).any()


def test_ragged_tile_metadata():
    """Each row tile's group, and the dW kernel's steps: every tile of a
    group in order (first and last flagged), one step for an empty group,
    and steps past the last real one with no flag."""
    from repro.kernels import grouped_matmul as gm
    gs = jnp.asarray([256, 0, 128], jnp.int32)
    group, n_live = gm.tile_groups(gs, 5, tm=128)
    assert list(np.asarray(group)) == [0, 0, 2, 2, 2] and int(n_live[0]) == 3
    group, tile, flags = gm.tile_items(gs, 5, tm=128)
    assert list(np.asarray(group)) == [0, 0, 1, 2, 2, 2, 2, 2]
    assert list(np.asarray(tile)[[0, 1, 3]]) == [0, 1, 2]
    F, L, V = gm.FIRST, gm.LAST, gm.LIVE
    assert list(np.asarray(flags)) == [F | V, L | V, F | L, F | L | V, 0, 0, 0, 0]


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_mla_widths(dtype):
    """q and k 192 wide, v 128 (multi-head latent attention): the Pallas
    forward and both backward kernels, in interpret mode through the TPU
    attention entry, against ``chunked_attention`` and its VJP."""
    from repro.models.attention import chunked_attention
    B, S, H, dqk, dv = 1, 256, 2, 192, 128
    q, k = (jnp.asarray(rng.randn(B, S, H, dqk) * 0.5, jnp.float32).astype(dtype)
            for _ in range(2))
    v = jnp.asarray(rng.randn(B, S, H, dv) * 0.5, jnp.float32).astype(dtype)
    do = jnp.asarray(rng.randn(B, S, H, dv), jnp.float32).astype(dtype)
    out, vjp = jax.vjp(lambda q, k, v: ops.flash_attention(q, k, v, interpret=True), q, k, v)
    assert out.shape == (B, S, H, dv)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    want, vjp_ref = jax.vjp(lambda q, k, v: chunked_attention(q, k, v, chunk=128), *f32[:3])
    _assert_rel_close((out,), (want,), BWD_RTOL[dtype])
    got = vjp(do)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    _assert_rel_close(got, vjp_ref(f32[3]), BWD_RTOL[dtype])
