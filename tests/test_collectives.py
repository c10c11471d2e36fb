"""HetCCL collective semantics: every hier op must equal its flat/native
equivalent, and the differentiable FSDP gather must have the right adjoint."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import collectives as C
from repro.core import compat, hetccl

rng = np.random.RandomState(0)


@pytest.fixture(autouse=True)
def _seed_inputs(request):
    """Each test draws its inputs from a seed of its own name, not from
    whatever the tests before it in this worker left in the shared
    generator (pytest-xdist orders tests differently from run to run)."""
    rng.seed(zlib.crc32(request.node.name.encode()))


def run(mesh, fn, x, in_spec, out_spec):
    sm = compat.shard_map(fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                          axis_names={"pod", "data"}, check_vma=False)
    return np.asarray(jax.jit(sm)(x))


def test_ring_reduce_scatter_matches_psum_scatter(mesh3):
    x = rng.randn(8, 6, 5).astype(np.float32)
    got = run(mesh3, lambda v: C.ring_reduce_scatter(v, "pod"), x,
              P(("pod", "data")), P(("pod", "data")))
    want = run(mesh3, lambda v: jax.lax.psum_scatter(
        v, "pod", scatter_dimension=0, tiled=True), x,
        P(("pod", "data")), P(("pod", "data")))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_ring_all_gather_matches_all_gather(mesh3):
    x = rng.randn(8, 7).astype(np.float32)
    got = run(mesh3, lambda v: C.ring_all_gather(v, "pod"), x,
              P(("pod", "data")), P("data"))
    want = run(mesh3, lambda v: jax.lax.all_gather(v, "pod", axis=0, tiled=True),
               x, P(("pod", "data")), P("data"))
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_ring_all_reduce_matches_psum(mesh3):
    x = rng.randn(2 * 5, 3).astype(np.float32)
    got = run(mesh3, lambda v: C.ring_all_reduce(v, "pod"), x, P("pod"), P("pod"))
    want = run(mesh3, lambda v: jax.lax.psum(v, "pod"), x, P("pod"), P("pod"))
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("shape", [(37, 3), (8,), (4, 4, 4)])
def test_hier_all_reduce_matches_flat(mesh3, shape):
    W = 4  # pod*data ranks
    x = rng.randn(W, *shape).astype(np.float32)

    def hier(v):
        return C.hier_all_reduce(v[0], ("data",), "pod")[None]

    def flat(v):
        return jax.lax.psum(v[0], ("pod", "data"))[None]

    got = run(mesh3, hier, x, P(("pod", "data")), P(("pod", "data")))
    want = run(mesh3, flat, x, P(("pod", "data")), P(("pod", "data")))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_hier_all_gather_pod_major_order(mesh3):
    x = rng.randn(4 * 2, 3).astype(np.float32)
    got = run(mesh3, lambda v: C.hier_all_gather(v, ("data",), "pod"), x,
              P(("pod", "data")), P(None))
    want = run(mesh3, lambda v: C.flat_all_gather(v, ("data",), "pod"), x,
               P(("pod", "data")), P(None))
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_hier_all_to_all_matches_flat(mesh3):
    x = rng.randn(4, 4 * 3, 5).astype(np.float32)

    def h(v):
        return C.hier_all_to_all(v[0], ("data",), "pod")[None]

    def f(v):
        return C.flat_all_to_all(v[0], ("data",), "pod")[None]

    got = run(mesh3, h, x, P(("pod", "data")), P(("pod", "data")))
    want = run(mesh3, f, x, P(("pod", "data")), P(("pod", "data")))
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_broadcast_and_reduce(mesh3):
    x = rng.randn(4, 6).astype(np.float32)
    got = run(mesh3, lambda v: C.hier_broadcast(v[0], ("data",), "pod", root=0)[None],
              x, P(("pod", "data")), P(("pod", "data")))
    np.testing.assert_allclose(got, np.broadcast_to(x[0], x.shape), atol=1e-6)
    red = run(mesh3, lambda v: C.hier_reduce(v[0], ("data",), "pod", root=0)[None],
              x, P(("pod", "data")), P(("pod", "data")))
    np.testing.assert_allclose(red[0], x.sum(0), rtol=1e-5)
    assert np.allclose(red[1:], 0)


def test_fsdp_all_gather_adjoint(mesh3):
    x = rng.randn(2 * 4, 3).astype(np.float32)

    def grad_fn(v):
        def loss(u):
            y = C.fsdp_all_gather(u, "data", 0)
            return jnp.sum(y ** 2) / jax.lax.axis_size("data")
        return jax.grad(loss)(v)

    got = run(mesh3, grad_fn, x, P("data"), P("data"))
    np.testing.assert_allclose(got, 2 * x, rtol=1e-5)


def test_tree_all_reduce_bucketing(mesh3):
    tree = {"a": rng.randn(4, 11).astype(np.float32),
            "b": rng.randn(4, 3, 5).astype(np.float32)}
    cfg = hetccl.HetCCLConfig(mode="hier", local_axes=("data",),
                              pod_axis="pod", bucket_bytes=64)

    def f(a, b):
        out = hetccl.tree_all_reduce({"a": a[0], "b": b[0]}, cfg)
        return out["a"][None], out["b"][None]

    sm = compat.shard_map(f, mesh=mesh3,
                          in_specs=(P(("pod", "data")), P(("pod", "data"))),
                          out_specs=(P(("pod", "data")), P(("pod", "data"))),
                          axis_names={"pod", "data"}, check_vma=False)
    ga, gb = jax.jit(sm)(tree["a"][:, None], tree["b"][:, None])
    np.testing.assert_allclose(np.asarray(ga)[0, 0], tree["a"].sum(0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gb)[0, 0], tree["b"].sum(0), rtol=1e-5)


def test_cross_dtype_compression(mesh3):
    """Cross-pod stage compressed to bf16: result close to exact sum."""
    x = rng.randn(4, 64).astype(np.float32)

    def f(v):
        return C.hier_all_reduce(v[0], ("data",), "pod",
                                 cross_dtype=jnp.bfloat16)[None]

    got = run(mesh3, f, x, P(("pod", "data")), P(("pod", "data")))
    np.testing.assert_allclose(got[0], x.sum(0), rtol=2e-2, atol=2e-2)


def test_install_swaps_backend(mesh3):
    """The LD_PRELOAD analogue: install() changes the default variant."""
    from repro.core import tacc
    prev = hetccl.install(hetccl.HetCCLConfig(mode="hier", pod_axis="pod"))
    assert tacc.get_default("all_reduce") == "hier"
    hetccl.install(hetccl.HetCCLConfig(mode="flat", pod_axis=None))
    assert tacc.get_default("all_reduce") == "flat"
    hetccl.install(prev)
    hetccl.uninstall()
    hetccl.uninstall()
    hetccl.uninstall()


def test_uninstall_restores_registry_defaults():
    """install() mutates the TACC defaults; uninstall() must restore them —
    nested/test-scoped backend swaps may not leak state (regression)."""
    from repro.core import tacc
    before_cfg = hetccl.current()
    before = {op: tacc.get_default(op)
              for op in ("all_reduce", "all_gather", "reduce_scatter",
                         "broadcast", "reduce", "all_to_all")}
    hetccl.install(hetccl.HetCCLConfig(mode="hier", pod_axis="pod"))
    hetccl.install(hetccl.HetCCLConfig(mode="pipelined", pod_axis="pod"))
    assert tacc.get_default("all_reduce") == "pipelined"
    assert tacc.get_default("broadcast") == "hier"   # graceful fallback
    hetccl.uninstall()
    assert tacc.get_default("all_reduce") == "hier"
    hetccl.uninstall()
    assert {op: tacc.get_default(op) for op in before} == before
    assert hetccl.current() == before_cfg
    # idempotent on an empty stack
    hetccl.uninstall()
    assert {op: tacc.get_default(op) for op in before} == before


def test_use_context_manager_scopes_backend():
    from repro.core import tacc
    before = tacc.get_default("all_reduce")
    with pytest.raises(RuntimeError):
        with hetccl.use(hetccl.HetCCLConfig(mode="hier", pod_axis="pod")):
            assert tacc.get_default("all_reduce") == "hier"
            raise RuntimeError("boom")                # exits still restore
    assert tacc.get_default("all_reduce") == before


def test_nested_use_with_repeated_config():
    """use() must stay LIFO-balanced even when the inner config equals the
    config the outer install displaced (no install()-undo shortcut)."""
    from repro.core import tacc
    cfg0 = hetccl.current()
    a = hetccl.HetCCLConfig(mode="hier", pod_axis="pod")
    with hetccl.use(a):
        with hetccl.use(cfg0):
            assert hetccl.current() == cfg0
        assert hetccl.current() == a                  # outer scope intact
        assert tacc.get_default("all_reduce") == "hier"
    assert hetccl.current() == cfg0


def test_install_invalid_mode_leaves_state_untouched():
    from repro.core import tacc
    before = tacc.get_default("all_reduce")
    cfg0 = hetccl.current()
    depth = len(hetccl._INSTALL_STACK)
    with pytest.raises(ValueError):
        hetccl.install(hetccl.HetCCLConfig(mode="heir", pod_axis="pod"))
    assert hetccl.current() == cfg0
    assert len(hetccl._INSTALL_STACK) == depth
    assert tacc.get_default("all_reduce") == before


# ---------------------------------------------------------------------------
# Pipelined multi-channel variants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_channels", [1, 2, 4, 7])
@pytest.mark.parametrize("shape", [(37, 3), (8,), (4, 4, 4), (3,)])
def test_pipelined_all_reduce_matches_flat(mesh3, shape, n_channels):
    x = rng.randn(4, *shape).astype(np.float32)

    def pipe(v):
        return C.pipelined_all_reduce(v[0], ("data",), "pod",
                                      n_channels=n_channels)[None]

    def flat(v):
        return jax.lax.psum(v[0], ("pod", "data"))[None]

    got = run(mesh3, pipe, x, P(("pod", "data")), P(("pod", "data")))
    want = run(mesh3, flat, x, P(("pod", "data")), P(("pod", "data")))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_channels", [1, 2, 3])
def test_pipelined_all_gather_matches_flat(mesh3, n_channels):
    x = rng.randn(4 * 5, 3).astype(np.float32)
    got = run(mesh3, lambda v: C.pipelined_all_gather(
        v, ("data",), "pod", n_channels=n_channels), x,
        P(("pod", "data")), P(None))
    want = run(mesh3, lambda v: C.flat_all_gather(v, ("data",), "pod"), x,
               P(("pod", "data")), P(None))
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("n_channels", [1, 2, 5])
def test_pipelined_reduce_scatter_matches_flat(mesh3, n_channels):
    x = rng.randn(4 * 4 * 3, 2).astype(np.float32)
    got = run(mesh3, lambda v: C.pipelined_reduce_scatter(
        v, ("data",), "pod", n_channels=n_channels), x, P(None),
        P(("pod", "data")))
    want = run(mesh3, lambda v: C.flat_reduce_scatter(v, ("data",), "pod"), x,
               P(None), P(("pod", "data")))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_pipelined_chunk_bytes_sizing(mesh3):
    """pipeline_chunk_bytes is an alternative to n_channels: ~chunk-sized
    splits, same numerics."""
    x = rng.randn(4, 64).astype(np.float32)

    def pipe(v):
        return C.pipelined_all_reduce(v[0], ("data",), "pod",
                                      pipeline_chunk_bytes=64)[None]

    got = run(mesh3, pipe, x, P(("pod", "data")), P(("pod", "data")))
    np.testing.assert_allclose(got[0], x.sum(0), rtol=1e-5)


def test_resolve_channels_clamps():
    """Channel sizing edge cases: n_channels > payload granularity, explicit
    chunk_bytes ceil, MAX_CHANNELS bound, degenerate limits.  Payloads sit
    above the MXU-tile floor so these cases test exactly what they always
    did; the floor itself is tested separately below."""
    from repro.transport.stripe import MXU_TILE_BYTES
    rc = C.resolve_channels
    big = 64 * MXU_TILE_BYTES                          # comfortably splittable
    assert rc(big, 4, None, limit=64) == 4             # plain channel count
    assert rc(big, 16, None, limit=3) == 3             # n_channels > n_chunks
    assert rc(big, 999, None, limit=999) == C.MAX_CHANNELS
    assert rc(big, 0, None, limit=8) == 1              # nonsense -> serial
    assert rc(big, 4, big // 3, limit=64) == 4         # ceil(n/(n/3)) = 4
    assert rc(big, 4, 2 * big, limit=64) == 1          # chunk > payload
    assert rc(big, 4, None, limit=0) == 1              # empty granularity
    assert rc(0, 4, 256, limit=8) == 1                 # zero-byte payload


def test_resolve_channels_tile_floor():
    """Regression (DESIGN.md §11): channels × stripes must never fragment a
    payload below one MXU tile — a tiny gradient bucket runs one wide
    channel, not MAX_CHANNELS tile-starved ones."""
    from repro.transport.stripe import MXU_TILE_BYTES
    rc = C.resolve_channels
    assert rc(1024, 16, None, limit=999) == 1          # tiny bucket -> serial
    assert rc(4 * MXU_TILE_BYTES, 16, None, limit=999) == 4
    # stripes multiply the fragmentation: the same payload takes fewer
    # channels when each channel is further sliced over 4 links
    assert rc(16 * MXU_TILE_BYTES, 16, None, limit=999, n_stripes=1) == 16
    assert rc(16 * MXU_TILE_BYTES, 16, None, limit=999, n_stripes=4) == 4
    # explicit chunk_bytes is clamped by the same floor
    assert rc(4 * MXU_TILE_BYTES, 1, 512, limit=999, n_stripes=2) == 2


@pytest.mark.parametrize("n_channels", [8, 16])
def test_pipelined_channels_exceed_chunks(mesh3, n_channels):
    """More channels than the payload has elements per rank: the clamp must
    degrade to a correct (fewer-channel) schedule, not crash or pad-corrupt."""
    x = rng.randn(4, 3).astype(np.float32)             # 3 elements per rank

    def pipe(v):
        return C.pipelined_all_reduce(v[0], ("data",), "pod",
                                      n_channels=n_channels)[None]

    got = run(mesh3, pipe, x, P(("pod", "data")), P(("pod", "data")))
    np.testing.assert_allclose(got[0], x.sum(0), rtol=1e-5, atol=1e-6)
    y = rng.randn(4 * 2, 2).astype(np.float32)         # 2 rows per rank
    got = run(mesh3, lambda v: C.pipelined_reduce_scatter(
        v, ("data",), "pod", n_channels=n_channels), y, P(None),
        P(("pod", "data")))
    want = run(mesh3, lambda v: C.flat_reduce_scatter(v, ("data",), "pod"), y,
               P(None), P(("pod", "data")))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_two_rank_degenerate_rings():
    """n=2 rings: both directions share one link-pair, bidir must still hold;
    mixed-wire and broadcast roots included (the production multi-pod mesh
    has 2-rank cross rings per DP lane)."""
    mesh = _ring_mesh(2)

    def go(fn, v, ins, outs):
        sm = compat.shard_map(fn, mesh=mesh, in_specs=ins, out_specs=outs,
                              axis_names={"pod"}, check_vma=False)
        return np.asarray(jax.jit(sm)(v))

    x = rng.randn(2 * 2 * 3, 5).astype(np.float32)
    got = go(lambda v: C.ring_reduce_scatter_bidir(v, "pod"), x, P("pod"),
             P("pod"))
    want = go(lambda v: jax.lax.psum_scatter(
        v, "pod", scatter_dimension=0, tiled=True), x, P("pod"), P("pod"))
    np.testing.assert_allclose(got, want, atol=1e-5)
    got = go(lambda v: C.ring_reduce_scatter_mixed(
        v, "pod", wire_dtype=jnp.bfloat16), x, P("pod"), P("pod"))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    # single-row-per-rank chunk: bidir falls back to the unidirectional ring
    y = rng.randn(2 * 2 * 1, 3).astype(np.float32)
    got = go(lambda v: C.ring_reduce_scatter_bidir(v, "pod"), y, P("pod"),
             P("pod"))
    want = go(lambda v: jax.lax.psum_scatter(
        v, "pod", scatter_dimension=0, tiled=True), y, P("pod"), P("pod"))
    np.testing.assert_allclose(got, want, atol=1e-5)
    for root in (0, 1):
        z = rng.randn(2, 6).astype(np.float32)
        got = go(lambda v: C.ring_broadcast(v[0], "pod", root=root)[None], z,
                 P("pod"), P("pod"))
        np.testing.assert_allclose(got, np.broadcast_to(z[root], z.shape),
                                   atol=1e-6)


def test_pipelined_variant_registered():
    from repro.core import tacc
    for op in ("all_reduce", "all_gather", "reduce_scatter"):
        assert "pipelined" in tacc.variants(op), op


def test_pipelined_cross_dtype_compression(mesh3):
    x = rng.randn(4, 64).astype(np.float32)

    def f(v):
        return C.pipelined_all_reduce(v[0], ("data",), "pod", n_channels=2,
                                      cross_dtype=jnp.bfloat16)[None]

    got = run(mesh3, f, x, P(("pod", "data")), P(("pod", "data")))
    np.testing.assert_allclose(got[0], x.sum(0), rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Bidirectional rings + broadcast root
# ---------------------------------------------------------------------------

def _ring_mesh(n):
    """1-axis mesh of n devices (odd sizes included; mesh3 only has even)."""
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), ("pod",))


@pytest.mark.parametrize("n", [2, 3, 4, 5, 8])
def test_bidir_rings_match_unidirectional(n):
    mesh = _ring_mesh(n)
    # per-rank tile (n*3, 5): ring reduce-scatter needs n | local rows
    x = rng.randn(n * n * 3, 5).astype(np.float32)

    def go(fn, v, ins, outs):
        sm = compat.shard_map(fn, mesh=mesh, in_specs=ins, out_specs=outs,
                              axis_names={"pod"}, check_vma=False)
        return np.asarray(jax.jit(sm)(v))

    got = go(lambda v: C.ring_reduce_scatter_bidir(v, "pod"), x, P("pod"), P("pod"))
    want = go(lambda v: C.ring_reduce_scatter(v, "pod"), x, P("pod"), P("pod"))
    np.testing.assert_allclose(got, want, atol=1e-5)

    y = rng.randn(n * 4, 3).astype(np.float32)
    got = go(lambda v: C.ring_all_gather_bidir(v, "pod"), y, P("pod"), P(None))
    want = go(lambda v: C.ring_all_gather(v, "pod"), y, P("pod"), P(None))
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("n", [3, 4])
@pytest.mark.parametrize("root", [0, 1, 2])
def test_ring_broadcast_nonzero_root(n, root):
    mesh = _ring_mesh(n)
    x = rng.randn(n, 6).astype(np.float32)

    def f(v):
        return C.ring_broadcast(v[0], "pod", root=root)[None]

    sm = compat.shard_map(f, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                          axis_names={"pod"}, check_vma=False)
    got = np.asarray(jax.jit(sm)(x))
    np.testing.assert_allclose(got, np.broadcast_to(x[root], x.shape),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# tree_all_reduce bucketing edge cases + pipelined schedule equivalence
# ---------------------------------------------------------------------------

def _tree_reduce_on_mesh(mesh, tree, cfg, mean_by=None):
    leaves, treedef = jax.tree.flatten(tree)

    def f(*ls):
        out = hetccl.tree_all_reduce(
            jax.tree.unflatten(treedef, [l[0] for l in ls]), cfg,
            mean_by=mean_by)
        return tuple(o[None] for o in jax.tree.leaves(out))

    sm = compat.shard_map(f, mesh=mesh,
                          in_specs=(P(("pod", "data")),) * len(leaves),
                          out_specs=(P(("pod", "data")),) * len(leaves),
                          axis_names={"pod", "data"}, check_vma=False)
    outs = jax.jit(sm)(*[l[:, None] for l in leaves])
    return jax.tree.unflatten(treedef, [np.asarray(o)[0, 0] for o in outs])


@pytest.mark.parametrize("mode", ["flat", "hier", "pipelined"])
def test_tree_all_reduce_single_leaf_larger_than_bucket(mesh3, mode):
    big = rng.randn(4, 777).astype(np.float32)        # 3108 B >> 64 B buckets
    cfg = hetccl.HetCCLConfig(mode=mode, local_axes=("data",), pod_axis="pod",
                              bucket_bytes=64, n_channels=2)
    out = _tree_reduce_on_mesh(mesh3, {"w": big}, cfg)
    np.testing.assert_allclose(out["w"], big.sum(0), rtol=1e-5, atol=1e-5)


def test_tree_all_reduce_mixed_dtypes_and_int_mean(mesh3):
    """Mixed f32/bf16/int32 leaves: dtype-pure buckets; integer leaves are
    summed exactly and NOT divided by mean_by."""
    tree = {"f": rng.randn(4, 33).astype(np.float32),
            "h": rng.randn(4, 17).astype(np.float32),
            "n": (rng.rand(4, 9) * 10).astype(np.int32)}
    cfg = hetccl.HetCCLConfig(mode="hier", local_axes=("data",),
                              pod_axis="pod", bucket_bytes=64)
    mean = jnp.asarray(4.0, jnp.float32)
    out = _tree_reduce_on_mesh(mesh3, tree, cfg, mean_by=mean)
    np.testing.assert_allclose(out["f"], tree["f"].sum(0) / 4.0, rtol=1e-5)
    np.testing.assert_allclose(out["h"], tree["h"].sum(0) / 4.0, rtol=1e-5)
    np.testing.assert_array_equal(out["n"], tree["n"].sum(0))


@pytest.mark.parametrize("mode", ["flat", "hier", "pipelined"])
def test_tree_all_reduce_equals_per_leaf_psum(mesh3, mode):
    """The pipelined RS->AG schedule across buckets == per-leaf lax.psum."""
    tree = {"a": rng.randn(4, 11).astype(np.float32),
            "b": rng.randn(4, 3, 5).astype(np.float32),
            "c": rng.randn(4, 2).astype(np.float32)}
    cfg = hetccl.HetCCLConfig(mode=mode, local_axes=("data",), pod_axis="pod",
                              bucket_bytes=48, n_channels=2)
    out = _tree_reduce_on_mesh(mesh3, tree, cfg)
    for k in tree:
        np.testing.assert_allclose(out[k], tree[k].sum(0), rtol=1e-5,
                                   atol=1e-5, err_msg=f"{mode}/{k}")
