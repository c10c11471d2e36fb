"""The DeepSeek-V3 expert layer (``repro.models.moe.deepseek_moe``): the
dispatch plan, no token dropped however the routing skews, the same layer
through the Pallas ragged kernels (interpret mode) as through
``jax.lax.ragged_dot``, and the step's counters."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels  # noqa: F401  (registers kernel TACC entries)
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.core import compat, tacc
from repro.core.balance import uniform_plan
from repro.models import build, moe
from repro.train.trainer import _decays, make_train_program

T, D, F, E, K = 96, 32, 16, 8, 3


def _params(rng, held, shared=True):
    p = {"router": jnp.asarray(rng.randn(D, E) * 0.3, jnp.float32),
         "w1": jnp.asarray(rng.randn(held, D, F) * 0.2, jnp.float32),
         "w3": jnp.asarray(rng.randn(held, D, F) * 0.2, jnp.float32),
         "w2": jnp.asarray(rng.randn(held, F, D) * 0.2, jnp.float32)}
    if shared:
        p["shared"] = {"w1": jnp.asarray(rng.randn(D, 2 * F) * 0.2, jnp.float32),
                       "w3": jnp.asarray(rng.randn(D, 2 * F) * 0.2, jnp.float32),
                       "w2": jnp.asarray(rng.randn(2 * F, D) * 0.2, jnp.float32)}
    return p


def _layer(x, p, *, offset=0, held=E, alpha=0.0):
    return moe.deepseek_moe(x, p, n_seqs=2, top_k=K,
                            expert_offset=offset, n_held=held,
                            routed_scaling=2.5, norm_topk=True,
                            seq_aux_alpha=alpha)


def _dense(x, p, offset, held):
    """Every held expert on every token times its gate (zero where not
    chosen), plus the shared experts: the layer without any dispatch."""
    s = jax.nn.sigmoid(x @ p["router"])
    top = np.argsort(-np.asarray(s), axis=-1)[:, :K]
    gates = np.zeros((x.shape[0], E), np.float32)
    for t in range(x.shape[0]):
        g = np.asarray(s)[t, top[t]]
        gates[t, top[t]] = g / g.sum() * 2.5
    out = np.zeros(x.shape, np.float32)
    for j in range(held):
        h = jax.nn.silu(x @ p["w1"][j]) * (x @ p["w3"][j])
        out += gates[:, offset + j, None] * np.asarray(h @ p["w2"][j])
    if "shared" in p:
        sh = p["shared"]
        out += np.asarray((jax.nn.silu(x @ sh["w1"]) * (x @ sh["w3"])) @ sh["w2"])
    return out


def test_dispatch_plan_sorts_held_pairs_into_aligned_groups():
    idx = jnp.asarray([[0, 5], [2, 0], [7, 3], [2, 1]], jnp.int32)
    dest, row_token, sizes, padded = moe.dispatch_plan(idx, offset=0, held=4, tm=4)
    assert list(np.asarray(sizes)) == [2, 1, 2, 1]
    assert list(np.asarray(padded)) == [4, 4, 4, 4]
    R = row_token.shape[0]
    assert R == -(-(4 * 2 + 4 * 3) // 4) * 4
    # expert 0 holds tokens 0 and 1 (in token order), expert 2 tokens 1, 3
    assert list(np.asarray(row_token)[[0, 1, 4, 8, 9, 12]]) == [0, 1, 3, 1, 3, 2]
    assert int(np.asarray(dest)[1]) == R and int(np.asarray(dest)[4]) == R   # 5, 7
    assert (np.asarray(row_token)[[2, 3, 5, 6, 7, 10, 11]] == 4).all()


@pytest.mark.parametrize("variant", ["cpu", "interpret"])
@pytest.mark.parametrize("offset,held", [(0, E), (2, 4)], ids=["all", "share"])
def test_expert_layer_matches_dense_mixture(variant, offset, held):
    """Through ``jax.lax.ragged_dot`` and through the Pallas ragged kernels
    (interpret mode), the layer's output is the dense gate-weighted mixture
    of the experts it holds, plus the shared experts."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    p = _params(rng, held)
    tacc.set_platform(variant)
    try:
        out, aux = jax.jit(lambda x, p: _layer(x, p, offset=offset, held=held))(x, p)
    finally:
        tacc.set_platform_auto()
    np.testing.assert_allclose(np.asarray(out), _dense(x, p, offset, held), atol=1e-4)
    assert int(aux["moe_rows_held"]) <= T * K


def test_no_token_dropped_when_routing_skews():
    """All tokens route to expert 0 (and the next two): a group of every
    token, far past any capacity the softmax path would allow; each still
    gets its whole gate-weighted share."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(np.abs(rng.randn(T, D)) + 0.5, jnp.float32)
    p = _params(rng, E, shared=False)
    router = np.full((D, E), -1.0, np.float32)
    router[:, :K] = np.linspace(1.0, 0.5, K)
    p["router"] = jnp.asarray(router)
    out, aux = jax.jit(lambda x, p: _layer(x, p))(x, p)
    assert int(aux["moe_max_group"]) == T and int(aux["moe_rows_held"]) == T * K
    np.testing.assert_allclose(np.asarray(out), _dense(x, p, 0, E), atol=1e-4)


def test_balance_loss_is_deepseek_v3_eq_17_to_20():
    """alpha * sum_i f_i P_i per sequence, averaged over sequences; uniform
    scores and every expert chosen equally often read alpha exactly."""
    s = jnp.full((2 * E, E), 0.5)
    idx = jnp.asarray([[i % E, (i + 1) % E, (i + 2) % E] for i in range(2 * E)], jnp.int32)
    got = moe.seq_balance_loss(s, idx, n_seqs=2, alpha=1e-4)
    np.testing.assert_allclose(float(got), 1e-4, rtol=1e-6)


def test_train_step_reports_expert_counters():
    """The reduced Moonlight config trains through make_train_program; the
    step's metrics carry the expert layers' rows held (summed over layers
    and micro-steps) and their largest group."""
    cfg = get_config("moonshot-v1-16b-a3b").reduced()
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    prog = make_train_program(build(cfg), mesh, RunConfig(zero_stage=1, collective_mode="flat",
                                                          param_dtype="float32", n_micro=2),
                              uniform_plan(1, 2, 2))
    state = prog.init_fn(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.RandomState(3).randint(0, cfg.vocab, (2, 2, 32)), jnp.int32)
    _, metrics = prog.step_fn(state, {"tokens": toks, "labels": toks})
    n_layers = cfg.n_layers - cfg.first_k_dense
    assert int(metrics["moe_rows_held"]) == n_layers * 2 * (2 * 32) * cfg.top_k
    assert 0 < int(metrics["moe_max_group"]) <= 2 * 32
    assert np.isfinite(float(metrics["loss"]))


def test_weight_decay_skips_vectors_stacked_or_not():
    """Decay applies to matrices only: the stacked RMSNorm scales (L, D)
    and MLA's latent norm are vectors per layer and are not decayed."""
    from repro.models.common import is_meta
    metas = build(get_config("moonshot-v1-16b-a3b").reduced()).abstract_params()
    decays = jax.tree.map(_decays, metas, is_leaf=is_meta)
    blocks = decays["blocks"]
    assert not blocks["ln1"] and not blocks["attn"]["kv_norm"]
    assert not decays["dense"]["0"]["ln2"] and not decays["final_norm"]
    assert blocks["attn"]["wq"] and blocks["moe"]["w1"] and blocks["moe"]["router"]
    assert decays["embed"] and decays["lm_head"]
