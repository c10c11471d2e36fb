"""Ahead-of-time compiles of the main path's TPU kernels for a described
v5e:2x2 host — no chip attached; the TPU compiler refuses here what the chip
would refuse (unaligned slices, VMEM overflow, auto-partitioned Mosaic
calls).  Each compile checks that the Pallas kernel is really in the program
(``tpu_custom_call``), not a jnp fallback.

The topology is described inside a module-scoped fixture (never at import):
only the worker that runs these tests loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import compat, tacc
from repro.kernels import flash_attention, ops, quant, ring_dma

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


@pytest.mark.parametrize("dtype,d", [(jnp.float32, 256), (jnp.bfloat16, 512)])
def test_flash_attention_forward_fits_vmem_at_wide_heads(one_chip, dtype, d):
    """Where 1024 x 1024 blocks would overfill the scoped VMEM (f32 heads of
    256, bf16 heads of 512), the blocks the shapes choose still compile."""
    q = _sds((1, S, 2, d), dtype, one_chip)
    assert "tpu_custom_call" in _hlo(ops.flash_attention, q, q, q)


# Moonlight-16B-A3B's latent attention at seq 8192, micro-batch 2: q, k 192
# wide, v 128 (configs/moonshot_v1_16b_a3b.py)
MLA = (2, 8192, 16, 16, 192, 128)


@pytest.mark.parametrize("b,s,hq,hkv,d,dv", [(B, S, HQ, HKV, D, D), MLA],
                         ids=["smollm", "mla"])
def test_flash_attention_vjp_compiles(one_chip, b, s, hq, hkv, d, dv):
    q = _sds((b, s, hq, d), jnp.bfloat16, one_chip)
    k = _sds((b, s, hkv, d), jnp.bfloat16, one_chip)
    v = _sds((b, s, hkv, dv), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v).astype(jnp.float32))

    # value_and_grad: the forward value keeps the kernel live in the program
    text = _hlo(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert "tpu_custom_call" in text
    for name in (flash_attention.KERNEL_NAME, flash_attention.BWD_DKV_KERNEL_NAME,
                 flash_attention.BWD_DQ_KERNEL_NAME):
        assert re.search(rf'op_name="[^"]*{name}\)?/pallas_call"', text), name


def test_ragged_grouped_matmul_vjp_compiles(one_chip):
    """Moonlight's expert products at their widths (2048 -> 1408, 8 held
    experts) over the worst-case buffer of 16,384 tokens x 6 choices: the
    forward and dX kernel and the dW kernel, each fetching an expert's
    whole weight into VMEM."""
    from repro.kernels import grouped_matmul as gm
    rows = -(-(16384 * 6 + 8 * (gm.ROW_TILE - 1)) // gm.ROW_TILE) * gm.ROW_TILE
    x = _sds((rows, 2048), jnp.bfloat16, one_chip)
    w = _sds((8, 2048, 1408), jnp.bfloat16, one_chip)
    sizes = _sds((8,), jnp.int32, one_chip)

    def loss(x, w, sizes):
        return jnp.sum(ops.ragged_grouped_matmul(x, w, sizes).astype(jnp.float32))

    text = _hlo(jax.value_and_grad(loss, argnums=(0, 1)), x, w, sizes)
    for name in (gm.GMM_KERNEL_NAME, gm.TGMM_KERNEL_NAME):   # dW: transpose(jvp(..))
        assert re.search(rf'op_name="[^"]*{name}\)*/pallas_call"', text), name


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


@pytest.mark.parametrize("arch, flash_calls", [("smollm-135m", 2),
                                               ("moonshot-v1-16b-a3b", 4)])
def test_one_micro_step_compiles_one_layer_loop_each_way(topo, tpu_platform,
                                                         arch, flash_calls):
    """A step of one micro-step holds the layer loop once forward and once
    backward: one forward and one remat ``_flash_kernel`` per attention
    layer in the program (the DeepSeek-V3 block's leading dense layer adds
    its own pair), one backward kernel pair each.  Through a length-1
    micro-step scan XLA had left the backward loop twice, and with the
    expert counters in the layer carry the forward too."""
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.core.balance import uniform_plan
    from repro.models import build
    from repro.train.trainer import make_train_program
    cfg = get_config(arch).reduced()
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    prog = make_train_program(build(cfg), mesh, RunConfig(zero_stage=1),
                              uniform_plan(1, 1, 2))
    state = jax.tree.map(lambda x, sh: _sds(x.shape, x.dtype, sh),
                         jax.eval_shape(prog.init_fn, jax.random.PRNGKey(0)),
                         prog.state_shardings)
    batch = {k: _sds(prog.batch_shape(256), jnp.int32, prog.batch_sharding[k])
             for k in ("tokens", "labels")}
    text = prog.step_fn.lower(state, batch).compile().as_text()

    def calls(name):
        return len(re.findall(rf'custom_call_target="tpu_custom_call".*?{name}\b', text))
    assert calls(flash_attention.KERNEL_NAME) == flash_calls
    assert calls(flash_attention.BWD_DKV_KERNEL_NAME) == flash_calls // 2
    assert calls(flash_attention.BWD_DQ_KERNEL_NAME) == flash_calls // 2
