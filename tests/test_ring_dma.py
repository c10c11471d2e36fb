"""Interpret-mode equivalence suite for the ``backend="pallas"`` collective
backend (DESIGN.md §10).

The DMA rings must be bit-equivalent (within dtype tolerance) to the xla
ppermute rings for reduce-scatter / all-gather / all-reduce across f32/bf16
payloads and flat/hier/pipelined modes.  The ``interpret_reduce`` fixture
pins the TACC ``collective_reduce`` entry to the Pallas kernel's
interpret-mode body, so the kernel's accumulate (f32 acc + narrow-wire
decompression) — the piece the TPU DMA kernel fuses — is what actually runs.
"""
import os

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import compat, hetccl, tacc
from repro.core import collectives as C
from repro.kernels import ring_dma

rng = np.random.RandomState(7)


@pytest.fixture(autouse=True)
def _seed_inputs(request):
    """Each test draws its inputs from a seed of its own name, not from
    whatever the tests before it in this worker left in the shared
    generator (pytest-xdist orders tests differently from run to run)."""
    rng.seed(zlib.crc32(request.node.name.encode()))

# CI matrix knobs: the pallas-equivalence job re-runs this whole suite with
# the transport stripe count forced to 2 (DESIGN.md §11) and again with the
# wire codec forced to int8 (DESIGN.md §17), so every mode-level equivalence
# below also certifies the striped and the quantized schedules.
N_STRIPES = int(os.environ.get("REPRO_TEST_N_STRIPES", "1"))
WIRE_QUANT = os.environ.get("REPRO_TEST_WIRE_QUANT", "none").lower()
WIRE_QUANT = None if WIRE_QUANT in ("", "none") else WIRE_QUANT

TOL = {np.float32: dict(rtol=1e-5, atol=1e-5),
       # bf16 payloads: the xla ring accumulates in bf16, the pallas ring in
       # f32 (collective_reduce contract) — equal within bf16 resolution
       "bfloat16": dict(rtol=5e-2, atol=5e-2)}
# A quantized wire is deliberately lossy: per-chunk absmax/127 grid
# resolution, re-quantized partials on the reduce path — equivalence to the
# xla ring holds within the codec's error envelope, not bitwise.
QTOL = dict(rtol=5e-2, atol=5e-2)


def _tol(dtype_key):
    return QTOL if WIRE_QUANT else TOL[dtype_key]


@pytest.fixture(scope="module", autouse=True)
def interpret_reduce():
    """Run every per-step accumulate through the Pallas kernel body in
    interpret mode (the interpret-mode contract of DESIGN.md §10)."""
    prev = tacc.get_default("collective_reduce")
    tacc.set_default("collective_reduce", "interpret")
    yield
    tacc.set_default("collective_reduce", prev)


def _run(mesh, fn, x, ins, outs, axes={"pod", "data"}):
    sm = compat.shard_map(fn, mesh=mesh, in_specs=ins, out_specs=outs,
                          axis_names=set(axes), check_vma=False)
    return np.asarray(jax.jit(sm)(x))


def _ring_mesh(n):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), ("pod",))


def _cfg(mode, backend, **kw):
    kw.setdefault("n_stripes", N_STRIPES)
    kw.setdefault("wire_quant", WIRE_QUANT)
    return hetccl.HetCCLConfig(mode=mode, local_axes=("data",),
                               pod_axis="pod", backend=backend, **kw)


# ---------------------------------------------------------------------------
# Ring primitives vs the xla rings (odd sizes, 2-rank degenerate, bidir)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 3, 5])
def test_dma_ring_reduce_scatter_matches_xla(n):
    mesh = _ring_mesh(n)
    x = rng.randn(n * n * 3, 4).astype(np.float32)
    got = _run(mesh, lambda v: ring_dma.ring_reduce_scatter(v, "pod"), x,
               P("pod"), P("pod"), {"pod"})
    want = _run(mesh, lambda v: C.ring_reduce_scatter(v, "pod"), x,
                P("pod"), P("pod"), {"pod"})
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [2, 3, 4])
def test_dma_ring_all_gather_matches_xla(n):
    mesh = _ring_mesh(n)
    x = rng.randn(n * 5, 3).astype(np.float32)
    got = _run(mesh, lambda v: ring_dma.ring_all_gather(v, "pod"), x,
               P("pod"), P(None), {"pod"})
    want = _run(mesh, lambda v: C.ring_all_gather(v, "pod"), x,
                P("pod"), P(None), {"pod"})
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("n", [2, 3, 5])
def test_dma_bidir_rings_match_unidirectional(n):
    mesh = _ring_mesh(n)
    x = rng.randn(n * n * 3, 5).astype(np.float32)
    got = _run(mesh, lambda v: ring_dma.ring_reduce_scatter_bidir(v, "pod"),
               x, P("pod"), P("pod"), {"pod"})
    want = _run(mesh, lambda v: C.ring_reduce_scatter(v, "pod"), x,
                P("pod"), P("pod"), {"pod"})
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    y = rng.randn(n * 4, 3).astype(np.float32)
    got = _run(mesh, lambda v: ring_dma.ring_all_gather_bidir(v, "pod"), y,
               P("pod"), P(None), {"pod"})
    want = _run(mesh, lambda v: C.ring_all_gather(v, "pod"), y,
                P("pod"), P(None), {"pod"})
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("n", [2, 3, 4])
def test_striped_rings_bit_equal(n, k):
    """Transport stripes (DESIGN.md §11) are pad-and-slice of the same wire
    hops: striped(k) == unstriped pallas == xla for RS and AG."""
    mesh = _ring_mesh(n)
    x = rng.randn(n * n * 2, 6).astype(np.float32)
    want = _run(mesh, lambda v: C.ring_reduce_scatter(v, "pod"), x,
                P("pod"), P("pod"), {"pod"})
    un = _run(mesh, lambda v: ring_dma.ring_reduce_scatter(v, "pod"), x,
              P("pod"), P("pod"), {"pod"})
    got = _run(mesh, lambda v: ring_dma.ring_reduce_scatter(
        v, "pod", n_stripes=k), x, P("pod"), P("pod"), {"pod"})
    np.testing.assert_array_equal(got, un)            # striping is exact
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    y = rng.randn(n * 4, 3).astype(np.float32)
    wag = _run(mesh, lambda v: C.ring_all_gather(v, "pod"), y,
               P("pod"), P(None), {"pod"})
    gag = _run(mesh, lambda v: ring_dma.ring_all_gather(
        v, "pod", n_stripes=k), y, P("pod"), P(None), {"pod"})
    np.testing.assert_array_equal(gag, wag)


@pytest.mark.parametrize("k", [2, 4])
def test_striped_all_reduce_matches_unstriped(k):
    mesh = _ring_mesh(4)
    x = rng.randn(4, 10, 7).astype(np.float32)
    got = _run(mesh, lambda v: ring_dma.ring_all_reduce(
        v[0], "pod", n_stripes=k)[None], x, P("pod"), P("pod"), {"pod"})
    un = _run(mesh, lambda v: ring_dma.ring_all_reduce(v[0], "pod")[None],
              x, P("pod"), P("pod"), {"pod"})
    np.testing.assert_array_equal(got, un)
    np.testing.assert_allclose(got[0], x.sum(0), rtol=1e-5, atol=1e-5)


def test_failover_restripe_same_numerics_higher_modeled_time():
    """The transport failover contract (DESIGN.md §11): a link marked down
    mid-plan restripes over the survivors — identical numerics (the stripe
    count only re-slices the same bytes), strictly accounted (priced) time."""
    from repro import transport
    from repro.core import simulator as sim
    from repro.core.topology import tpu_mixed_fleet
    fs = transport.FlowScheduler(transport.LinkInventory.from_chip(
        tpu_mixed_fleet().pods[0].chip), inter_bw=25e9)
    plan = fs.plan(32 << 20)
    mesh = _ring_mesh(4)
    x = rng.randn(4 * 8, 5).astype(np.float32)

    def run_k(k):
        return _run(mesh, lambda v: ring_dma.ring_reduce_scatter(
            v, "pod", n_stripes=k), x, P("pod"), P("pod"), {"pod"})

    before = run_k(plan.n_stripes)
    ev = fs.failover(plan, plan.link_ids[0], 32 << 20)
    after = run_k(ev.new_plan.n_stripes)
    np.testing.assert_array_equal(before, after)      # numerics unchanged
    assert ev.new_time_s > ev.old_time_s              # time is, and is priced
    # the simulator sees the same failover through the cluster inventory
    healthy, down = tpu_mixed_fleet(2, 2, 8), tpu_mixed_fleet(2, 2, 8)
    down.inventory(down.pods[0]).mark_down(0)
    assert sim.collective_time("all_reduce", 32 << 20, down, "pipelined",
                               backend="pallas", n_stripes="auto") > \
        sim.collective_time("all_reduce", 32 << 20, healthy, "pipelined",
                            backend="pallas", n_stripes="auto")


def test_dma_ring_narrow_wire_decompression():
    """wire_dtype=bf16 + f32 accumulator == ring_reduce_scatter_mixed (the
    collective_reduce semantics the TPU kernel fuses)."""
    mesh = _ring_mesh(4)
    x = rng.randn(4 * 8, 16).astype(np.float32)
    got = _run(mesh, lambda v: ring_dma.ring_reduce_scatter(
        v, "pod", wire_dtype=jnp.bfloat16), x, P("pod"), P("pod"), {"pod"})
    want = _run(mesh, lambda v: C.ring_reduce_scatter_mixed(
        v, "pod", wire_dtype=jnp.bfloat16).astype(np.float32), x,
        P("pod"), P("pod"), {"pod"})
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Backend equivalence through the public hetccl ops: all three modes,
# f32 and bf16 payloads.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
@pytest.mark.parametrize("mode", ["flat", "hier", "pipelined"])
def test_all_reduce_backend_equivalence(mesh3, mode, dtype):
    x = rng.randn(4, 37, 3).astype(np.float32)
    tol = _tol(dtype)

    def go(backend):
        cfg = _cfg(mode, backend, n_channels=2)

        def f(v):
            return hetccl.all_reduce(
                v[0].astype(jnp.bfloat16 if dtype == "bfloat16" else dtype),
                cfg).astype(np.float32)[None]
        return _run(mesh3, f, x, P(("pod", "data")), P(("pod", "data")))

    np.testing.assert_allclose(go("pallas"), go("xla"), **tol)


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
@pytest.mark.parametrize("mode", ["flat", "hier", "pipelined"])
def test_reduce_scatter_backend_equivalence(mesh3, mode, dtype):
    x = rng.randn(4 * 4 * 3, 2).astype(np.float32)
    tol = _tol(dtype)

    def go(backend):
        cfg = _cfg(mode, backend, n_channels=2)

        def f(v):
            return hetccl.reduce_scatter(
                v.astype(jnp.bfloat16 if dtype == "bfloat16" else dtype),
                cfg).astype(np.float32)
        return _run(mesh3, f, x, P(None), P(("pod", "data")))

    np.testing.assert_allclose(go("pallas"), go("xla"), **tol)


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
@pytest.mark.parametrize("mode", ["flat", "hier", "pipelined"])
def test_all_gather_backend_equivalence(mesh3, mode, dtype):
    x = rng.randn(4 * 5, 3).astype(np.float32)

    def go(backend):
        cfg = _cfg(mode, backend, n_channels=2)

        def f(v):
            return hetccl.all_gather(
                v.astype(jnp.bfloat16 if dtype == "bfloat16" else dtype),
                cfg).astype(np.float32)
        return _run(mesh3, f, x, P(("pod", "data")), P(None))

    # gather moves bytes verbatim: exact equality in both dtypes — except
    # under a wire codec, where the gathered values are the sender's grid
    # projection (encode once, forward codes verbatim)
    np.testing.assert_allclose(go("pallas"), go("xla"),
                               **(QTOL if WIRE_QUANT else dict(atol=0)))


def test_tree_all_reduce_pallas_backend(mesh3):
    """The bucketed gradient path composes with the pallas backend."""
    tree = {"a": rng.randn(4, 11).astype(np.float32),
            "b": rng.randn(4, 3, 5).astype(np.float32)}
    cfg = _cfg("pipelined", "pallas", bucket_bytes=64, n_channels=2)

    def f(a, b):
        out = hetccl.tree_all_reduce({"a": a[0], "b": b[0]}, cfg)
        return out["a"][None], out["b"][None]

    sm = compat.shard_map(f, mesh=mesh3,
                          in_specs=(P(("pod", "data")), P(("pod", "data"))),
                          out_specs=(P(("pod", "data")), P(("pod", "data"))),
                          axis_names={"pod", "data"}, check_vma=False)
    ga, gb = jax.jit(sm)(tree["a"][:, None], tree["b"][:, None])
    tol = dict(rtol=5e-2, atol=0.3) if WIRE_QUANT else dict(rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ga)[0, 0], tree["a"].sum(0), **tol)
    np.testing.assert_allclose(np.asarray(gb)[0, 0], tree["b"].sum(0), **tol)


def test_fsdp_adjoint_routes_through_installed_backend(mesh3):
    """ZeRO-3's gradient reduce-scatter (fsdp_all_gather adjoint) follows
    the installed backend and keeps the narrow-wire/f32 numerics."""
    x = rng.randn(2 * 4, 3).astype(np.float32)

    def grad_fn(v):
        def loss(u):
            y = C.fsdp_all_gather(u, "data", 0)
            return jnp.sum(y ** 2) / jax.lax.axis_size("data")
        return jax.grad(loss)(v)

    with hetccl.use(_cfg("hier", "pallas")):
        got = _run(mesh3, grad_fn, x, P("data"), P("data"))
    tol = dict(rtol=5e-2, atol=0.2) if WIRE_QUANT else dict(rtol=1e-5)
    np.testing.assert_allclose(got, 2 * x, **tol)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        C.resolve_ring_backend("cuda")
    with pytest.raises(ValueError):
        hetccl.HetCCLConfig(backend="cuda").resolved_backend()
    depth = len(hetccl._INSTALL_STACK)
    with pytest.raises(ValueError):
        hetccl.install(hetccl.HetCCLConfig(backend="cuda"))
    assert len(hetccl._INSTALL_STACK) == depth


def test_dma_streams_contract():
    """The simulator's overlap model and the kernel's double-buffer depth
    must describe the same schedule."""
    from repro.core import simulator as sim
    assert sim.DMA_STREAMS == ring_dma.NUM_BUFFERS
