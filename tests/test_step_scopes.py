"""The jitted train step names its layers in the compiled program
(DESIGN.md §16).

The step is compiled, not run, for a tiny dense model on 4 forced host
devices as ``(pod=2, data=2)``, hier collectives, ZeRO-1: every scope a
device trace attributes time by must appear in the HLO ``op_name``s.  The
attention entry is the TPU one (Pallas forward in interpret mode, custom
VJP), whose backward is the ``attention_bwd`` scope.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

import repro.kernels  # noqa: F401  (registers kernel TACC entries)
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.core import compat, tacc
from repro.core.balance import uniform_plan
from repro.core.collectives import CROSS_SCOPE, INTRA_SCOPE
from repro.kernels.flash_attention import (BWD_DKV_KERNEL_NAME,
                                           BWD_DQ_KERNEL_NAME)
from repro.kernels.ops import ATTENTION_BWD_SCOPE
from repro.models import build
from repro.models.transformer import (ATTENTION_SCOPE, EMBED_SCOPE,
                                      LM_HEAD_SCOPE, MLP_SCOPE)
from repro.train import optim
from repro.train.trainer import STEP_STATS_SCOPE, make_train_program

CFG = dataclasses.replace(get_config("smollm-135m").reduced(), n_layers=2)
SEQ = 64
_WRAPPER = re.compile(r"[A-Za-z_]+\(|\)")


def _paths(hlo_text: str) -> list[list[str]]:
    """Each op's name stack, wrappers such as ``transpose(jvp(...))``
    stripped."""
    return [[_WRAPPER.sub("", c) for c in name.split("/")]
            for name in re.findall(r'op_name="([^"]*)"', hlo_text)]


@pytest.fixture(scope="module", params=["xla", "pallas"])
def paths(request):
    mesh = compat.make_mesh((2, 2, 1), ("pod", "data", "model"),
                            devices=jax.devices()[:4])
    rc = RunConfig(zero_stage=1, collective_mode="hier",
                   backend=request.param, param_dtype="float32")
    tacc.set_platform("interpret")
    try:
        prog = make_train_program(build(CFG), mesh, rc, uniform_plan(2, 2, 1))
        state = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            jax.eval_shape(prog.init_fn, jax.random.PRNGKey(0)),
            prog.state_shardings)
        batch = {k: jax.ShapeDtypeStruct(prog.batch_shape(SEQ), jnp.int32,
                                         sharding=prog.batch_sharding[k])
                 for k in ("tokens", "labels")}
        text = prog.step_fn.lower(state, batch).compile().as_text()
    finally:
        tacc.set_platform_auto()
    return _paths(text)


@pytest.mark.parametrize("scope", [ATTENTION_SCOPE, ATTENTION_BWD_SCOPE,
                                   MLP_SCOPE, LM_HEAD_SCOPE, EMBED_SCOPE,
                                   optim.SCOPE, STEP_STATS_SCOPE])
def test_layer_scope_in_compiled_step(paths, scope):
    assert any(scope in p for p in paths), scope


def test_attention_backward_is_inside_the_attention_layer(paths):
    """The custom VJP's backward runs under the layer's transpose, so the
    innermost of the two names is the backward's."""
    inner = [p for p in paths if ATTENTION_BWD_SCOPE in p]
    assert all(ATTENTION_SCOPE in p[:p.index(ATTENTION_BWD_SCOPE)]
               for p in inner)


@pytest.mark.parametrize("kernel", [BWD_DKV_KERNEL_NAME, BWD_DQ_KERNEL_NAME])
def test_backward_kernels_are_inside_attention_bwd(paths, kernel):
    """The Pallas dK/dV and dQ kernels' operations carry the backward's
    scope, itself inside the attention layer's."""
    inner = [p for p in paths if kernel in p]
    assert inner, kernel
    for p in inner:
        before = p[:p.index(kernel)]
        assert ATTENTION_BWD_SCOPE in before
        assert ATTENTION_SCOPE in before[:before.index(ATTENTION_BWD_SCOPE)]


@pytest.mark.parametrize("op,stage", [("reduce_scatter", CROSS_SCOPE),
                                      ("reduce_scatter", INTRA_SCOPE),
                                      ("all_gather", INTRA_SCOPE),
                                      ("all_gather", CROSS_SCOPE)])
def test_collective_stage_under_its_dispatch_scope(paths, op, stage):
    """ZeRO-1's gradient reduce-scatter and parameter all-gather, each
    named ``hetccl.<op>.<size_class>.hier`` by the dispatch, with the
    stage nested below it, all under the optimizer."""
    name = re.compile(rf"hetccl\.{op}\.(small|medium|large)\.hier")
    found = [p for p in paths
             if any(name.fullmatch(c) for c in p) and stage in p]
    assert found, (op, stage)
    for p in found:
        i = next(i for i, c in enumerate(p) if name.fullmatch(c))
        assert stage in p[i + 1:] and optim.SCOPE in p[:i]

