"""Ahead-of-time compiles of the main path's TPU kernels for a described
v5e:2x2 host — no chip attached; the TPU compiler refuses here what the chip
would refuse (unaligned slices, VMEM overflow, auto-partitioned Mosaic
calls).  Each compile checks that the Pallas kernel is really in the program
(``tpu_custom_call``), not a jnp fallback.

The topology is described inside a module-scoped fixture (never at import):
only the worker that runs these tests loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import compat, tacc
from repro.kernels import ops, quant, ring_dma

BUCKET_ELEMS = (64 << 20) // 4          # the default 64 MiB f32 bucket


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_platform():
    """Steer TACC's platform-resolved paths (ring kernels) to the TPU
    branch for the duration of one compile."""
    tacc.set_platform("tpu")
    yield
    tacc.set_platform_auto()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# smollm-135m attention at seq 2048, micro-batch 4 (configs/smollm_135m.py)
B, S, HQ, HKV, D = 4, 2048, 9, 3, 64


def test_flash_attention_forward_compiles(one_chip):
    q = _sds((B, S, HQ, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, HKV, D), jnp.bfloat16, one_chip)
    assert "tpu_custom_call" in _hlo(ops.flash_attention, q, kv, kv)


def test_flash_attention_vjp_compiles(one_chip):
    q = _sds((B, S, HQ, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, HKV, D), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v).astype(jnp.float32))

    # value_and_grad: the forward value keeps the kernel live in the program
    assert "tpu_custom_call" in _hlo(
        jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)


def test_collective_reduce_compiles(one_chip):
    acc = _sds((BUCKET_ELEMS,), jnp.float32, one_chip)
    inc = _sds((BUCKET_ELEMS,), jnp.bfloat16, one_chip)
    assert "tpu_custom_call" in _hlo(ops.collective_reduce, acc, inc)


def test_int8_quant_kernels_compile(one_chip):
    rows = BUCKET_ELEMS // quant.DEFAULT_CHUNK
    x2 = _sds((rows, quant.DEFAULT_CHUNK), jnp.float32, one_chip)
    codes = _sds((rows, quant.DEFAULT_CHUNK), jnp.int8, one_chip)
    scales = _sds((rows, 1), jnp.float32, one_chip)
    assert "tpu_custom_call" in _hlo(quant.wire_quantize_pallas, x2)
    assert "tpu_custom_call" in _hlo(quant.wire_dequant_accum_pallas,
                                     x2, codes, scales)


@pytest.mark.parametrize("shape,stripes", [((4, 1, 1), 1), ((2, 2, 1), 2)],
                         ids=["pod4", "pod2xdata2-striped"])
@pytest.mark.parametrize("op", ["reduce_scatter", "all_gather"])
def test_dma_ring_compiles_at_default_bucket(topo, tpu_platform, op, shape,
                                             stripes):
    """The pallas ring over 'pod' inside the trainer's layout: 'pod'/'data'
    manual, 'model' auto — the kernel sits in the nested manual region."""
    mesh = Mesh(np.array(topo.devices).reshape(shape),
                ("pod", "data", "model"))
    n_dev = int(np.prod(shape))
    dp = P(("pod", "data"))
    if op == "reduce_scatter":
        def body(v):
            return ring_dma.ring_reduce_scatter(
                v, "pod", wire_dtype=jnp.bfloat16, n_stripes=stripes)
        per_dev = BUCKET_ELEMS
    else:
        def body(v):
            return ring_dma.ring_all_gather(v, "pod", n_stripes=stripes)
        per_dev = BUCKET_ELEMS // shape[0]
    fn = compat.shard_map(body, mesh=mesh, in_specs=dp, out_specs=dp,
                          axis_names={"pod", "data"})
    x = _sds((n_dev * per_dev,), jnp.float32, NamedSharding(mesh, dp))
    assert "tpu_custom_call" in _hlo(fn, x)
