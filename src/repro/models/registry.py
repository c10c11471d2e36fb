"""Model registry: one uniform interface over all families.

build(cfg) -> Model with:
  abstract_params() / init(key) / param_specs(rules)
  loss(params, batch, ctx)          -> (token-loss sum, token count, aux)
  prefill(params, batch, ctx)       -> (logits, cache)
  decode(params, cache, tokens, ctx)-> (logits, cache)
  cache_metas(batch, max_len)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec as encdec_mod
from repro.models import moe as moe_mod
from repro.models import transformer as tf
from repro.models.common import ParamMeta, init_params, shape_tree, spec_tree


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ---- parameters -------------------------------------------------------
    def abstract_params(self):
        if self.cfg.family == "encdec":
            return encdec_mod.abstract_params(self.cfg)
        return tf.abstract_params(self.cfg)

    def init(self, key, dtype=None):
        dtype = jnp.dtype(dtype or self.cfg.dtype)
        return init_params(key, self.abstract_params(), dtype)

    def param_shapes(self, dtype=None):
        return shape_tree(self.abstract_params(), jnp.dtype(dtype or self.cfg.dtype))

    def param_specs(self, rules):
        return spec_tree(self.abstract_params(), rules)

    def n_params(self) -> int:
        import numpy as np
        leaves = jax.tree.leaves(self.abstract_params(),
                                 is_leaf=lambda x: isinstance(x, ParamMeta))
        return int(sum(int(np.prod(m.shape)) for m in leaves))

    # ---- training ---------------------------------------------------------
    _SCANNED_KEYS = frozenset({"blocks", "groups", "tail", "shared",
                               "enc_blocks", "dec_blocks"})

    def _gather_top(self, params, ctx: tf.Ctx):
        """ZeRO-3: explicitly gather the non-scanned leaves (embed, lm_head,
        norms, pos tables) over 'data' before use."""
        if not ctx.manual:
            return params
        metas = self.abstract_params()
        top = {k: v for k, v in metas.items() if k not in self._SCANNED_KEYS}
        gplan = tf.gather_plan_of(top, ctx.rules, scanned=False)
        gathered = tf.maybe_gather({k: params[k] for k in top}, gplan)
        return {**params, **gathered}

    def loss(self, params, batch, ctx: tf.Ctx):
        """Returns (sum of token CE losses, token count, aux): aux["loss"]
        is the auxiliary loss per token, beside it the counters that
        :meth:`counters` names."""
        cfg = self.cfg
        params = self._gather_top(params, ctx)
        if cfg.family == "encdec":
            hidden, aux = encdec_mod.forward(params, batch, cfg, ctx)
        else:
            hidden, aux = tf.forward_lm(params, batch["tokens"], cfg, ctx,
                                        mrope=batch.get("mrope"))
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(batch["labels"], jnp.float32)
        loss_sum, count = tf.lm_loss_from_hidden(params, hidden, batch["labels"],
                                                 mask, cfg, ctx)
        return loss_sum, count, aux

    def counters(self) -> tuple[str, ...]:
        """Names of the counters the forward reports beside its auxiliary
        loss (the DeepSeek-V3 expert layer's; none for other blocks)."""
        cfg = self.cfg
        return tuple(moe_mod.COUNTERS) if cfg.router == "sigmoid" and cfg.n_experts else ()

    # ---- serving ----------------------------------------------------------
    def prefill(self, params, batch, ctx: tf.Ctx, max_len: int | None = None):
        if self.cfg.family == "encdec":
            return encdec_mod.prefill(params, batch, self.cfg, ctx,
                                      max_len=max_len)
        return tf.prefill_lm(params, batch["tokens"], self.cfg, ctx,
                             mrope=batch.get("mrope"), max_len=max_len)

    def decode(self, params, cache, tokens, ctx: tf.Ctx):
        if self.cfg.family == "encdec":
            return encdec_mod.decode_step(params, cache, tokens, self.cfg, ctx)
        return tf.decode_lm(params, cache, tokens, self.cfg, ctx)

    def cache_metas(self, batch: int, max_len: int):
        if self.cfg.family == "encdec":
            hd = self.cfg.head_dim_
            L = self.cfg.n_layers
            return {
                "k": ParamMeta((L, batch, max_len, self.cfg.n_kv_heads, hd),
                               ("layers", "cbatch", "cseq", "kv_heads", "head"), "zeros"),
                "v": ParamMeta((L, batch, max_len, self.cfg.n_kv_heads, hd),
                               ("layers", "cbatch", "cseq", "kv_heads", "head"), "zeros"),
                "cross_k": ParamMeta((L, batch, self.cfg.n_frames, self.cfg.n_kv_heads, hd),
                                     ("layers", "cbatch", "frames", "kv_heads", "head"), "zeros"),
                "cross_v": ParamMeta((L, batch, self.cfg.n_frames, self.cfg.n_kv_heads, hd),
                                     ("layers", "cbatch", "frames", "kv_heads", "head"), "zeros"),
                "pos": ParamMeta((), (), "zeros"),
            }
        return tf.cache_metas(self.cfg, batch, max_len)


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)
