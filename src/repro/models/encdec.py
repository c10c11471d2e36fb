"""Whisper-style encoder-decoder (audio frontend stubbed).

The conv/audio frontend is a STUB per the assignment: ``input_specs()``
provides precomputed frame embeddings (B, n_frames, d_model) that already
include the conv downsampling + sinusoidal positions.  Everything after that
— 24 bidirectional encoder layers, 24 causal decoder layers with
cross-attention, LayerNorm + GELU MLPs with biases, learned decoder position
embeddings — is implemented here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models.common import ParamMeta, layer_norm
from repro.models.transformer import (Ctx, _attn_metas, _mlp_metas,
                                      attn_sublayer, gather_plan_of,
                                      lm_logits, maybe_gather, mlp_sublayer)

MAX_DEC_POS = 32768


def abstract_params(cfg: ModelConfig) -> dict:
    D, V = cfg.d_model, cfg.padded_vocab
    Le, Ld = cfg.n_enc_layers, cfg.n_layers

    def lns(L, names):
        out = {}
        for n in names:
            out[n] = ParamMeta((L, D), ("layers", "embed"), "ones")
            out[n + "_b"] = ParamMeta((L, D), ("layers", "embed"), "zeros")
        return out

    return {
        "enc_blocks": {
            **lns(Le, ("ln1", "ln2")),
            "attn": _attn_metas(cfg, L=Le, bias=True),
            "mlp": _mlp_metas(cfg, L=Le, gated=False, bias=True),
        },
        "enc_norm": ParamMeta((D,), ("embed",), "ones"),
        "enc_norm_b": ParamMeta((D,), ("embed",), "zeros"),
        "embed": ParamMeta((V, D), ("vocab", "embed"), "normal", 0.02),
        "pos_embed": ParamMeta((MAX_DEC_POS, D), (None, "embed"), "normal", 0.01),
        "dec_blocks": {
            **lns(Ld, ("ln1", "ln2", "ln3")),
            "self_attn": _attn_metas(cfg, L=Ld, bias=True),
            "cross_attn": _attn_metas(cfg, L=Ld, bias=True),
            "mlp": _mlp_metas(cfg, L=Ld, gated=False, bias=True),
        },
        "final_norm": ParamMeta((D,), ("embed",), "ones"),
        "final_norm_b": ParamMeta((D,), ("embed",), "zeros"),
        "lm_head": ParamMeta((D, V), ("embed", "vocab")),
    }


def _cross_kv(p, enc_out, cfg):
    k = jnp.einsum("bfd,dhk->bfhk", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bfd,dhk->bfhk", enc_out, p["wv"].astype(enc_out.dtype))
    if "bv" in p:
        v = v + p["bv"].astype(enc_out.dtype)
    return k, v


def _cross_attend(p, h, ck, cv, cfg, ctx):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(h.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(h.dtype)
    out = attn_mod.attention(q, ck, cv, kind="bidir", chunk=cfg.attn_chunk)
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(h.dtype))
    if "bo" in p:
        proj = proj + p["bo"].astype(h.dtype)
    return proj


def encode(params, frames, cfg: ModelConfig, ctx: Ctx):
    """frames (B, F, D) -> encoder output (B, F, D)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    x = ctx.wsc(x, "batch", "seq", None)
    positions = jnp.arange(frames.shape[1])[None, :]
    gplan = (gather_plan_of(abstract_params(cfg)["enc_blocks"], ctx.rules, True)
             if ctx.manual else None)

    def body(h, lp):
        if gplan is not None:
            lp = maybe_gather(lp, gplan)
        hn = layer_norm(h, lp["ln1"].astype(jnp.float32),
                        lp["ln1_b"].astype(jnp.float32), cfg.norm_eps)
        a, _ = attn_sublayer(lp["attn"], hn, positions, cfg, ctx, kind="bidir")
        h = h + a
        hn = layer_norm(h, lp["ln2"].astype(jnp.float32),
                        lp["ln2_b"].astype(jnp.float32), cfg.norm_eps)
        h = h + mlp_sublayer(lp["mlp"], hn, cfg, ctx)
        return h, None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["enc_blocks"])
    return layer_norm(x, params["enc_norm"].astype(jnp.float32),
                      params["enc_norm_b"].astype(jnp.float32), cfg.norm_eps)


def decode_train(params, tokens, enc_out, cfg: ModelConfig, ctx: Ctx):
    """Teacher-forced decoder forward -> final hidden (B, S, D)."""
    B, S = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], 0, S, 0).astype(dtype)
    x = ctx.wsc(x, "batch", "seq", None)
    positions = jnp.arange(S)[None, :]
    gplan = (gather_plan_of(abstract_params(cfg)["dec_blocks"], ctx.rules, True)
             if ctx.manual else None)

    def body(h, lp):
        if gplan is not None:
            lp = maybe_gather(lp, gplan)
        hn = layer_norm(h, lp["ln1"].astype(jnp.float32),
                        lp["ln1_b"].astype(jnp.float32), cfg.norm_eps)
        a, _ = attn_sublayer(lp["self_attn"], hn, positions, cfg, ctx, kind="causal")
        h = h + a
        hn = layer_norm(h, lp["ln2"].astype(jnp.float32),
                        lp["ln2_b"].astype(jnp.float32), cfg.norm_eps)
        ck, cv = _cross_kv(lp["cross_attn"], enc_out, cfg)
        h = h + _cross_attend(lp["cross_attn"], hn, ck, cv, cfg, ctx)
        hn = layer_norm(h, lp["ln3"].astype(jnp.float32),
                        lp["ln3_b"].astype(jnp.float32), cfg.norm_eps)
        h = h + mlp_sublayer(lp["mlp"], hn, cfg, ctx)
        return h, None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["dec_blocks"])
    return layer_norm(x, params["final_norm"].astype(jnp.float32),
                      params["final_norm_b"].astype(jnp.float32), cfg.norm_eps)


def forward(params, batch, cfg: ModelConfig, ctx: Ctx):
    enc_out = encode(params, batch["frames"], cfg, ctx)
    return decode_train(params, batch["tokens"], enc_out, cfg, ctx), {"loss": jnp.zeros((), jnp.float32)}


def prefill(params, batch, cfg: ModelConfig, ctx: Ctx,
            max_len: int | None = None):
    """Encode + decoder prefill.  Returns (last logits, cache)."""
    enc_out = encode(params, batch["frames"], cfg, ctx)
    tokens = batch["tokens"]
    B, S = tokens.shape
    max_len = max(max_len or S, S)
    dtype = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], 0, S, 0).astype(dtype)
    positions = jnp.arange(S)[None, :]

    def body(h, lp):
        hn = layer_norm(h, lp["ln1"].astype(jnp.float32),
                        lp["ln1_b"].astype(jnp.float32), cfg.norm_eps)
        zk = jnp.zeros((B, max_len, cfg.n_kv_heads, cfg.head_dim_), dtype)
        a, kv = attn_sublayer(lp["self_attn"], hn, positions, cfg, ctx,
                              kind="causal", cache=(zk, zk),
                              pos=jnp.zeros((), jnp.int32))
        h = h + a
        hn = layer_norm(h, lp["ln2"].astype(jnp.float32),
                        lp["ln2_b"].astype(jnp.float32), cfg.norm_eps)
        ck, cv = _cross_kv(lp["cross_attn"], enc_out, cfg)
        h = h + _cross_attend(lp["cross_attn"], hn, ck, cv, cfg, ctx)
        hn = layer_norm(h, lp["ln3"].astype(jnp.float32),
                        lp["ln3_b"].astype(jnp.float32), cfg.norm_eps)
        h = h + mlp_sublayer(lp["mlp"], hn, cfg, ctx)
        return h, (kv[0], kv[1], ck.astype(dtype), cv.astype(dtype))

    x, (ks, vs, cks, cvs) = jax.lax.scan(jax.checkpoint(body), x, params["dec_blocks"])
    x = layer_norm(x, params["final_norm"].astype(jnp.float32),
                   params["final_norm_b"].astype(jnp.float32), cfg.norm_eps)
    logits = lm_logits(params, x[:, -1:], cfg, ctx)
    cache = {"k": ks, "v": vs, "cross_k": cks, "cross_v": cvs,
             "pos": jnp.asarray(S, jnp.int32)}
    return logits, cache


def decode_step(params, cache, tokens, cfg: ModelConfig, ctx: Ctx):
    """One decoder token with cached self/cross KV."""
    pos = cache["pos"].astype(jnp.int32)
    B = tokens.shape[0]
    dtype = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], pos, 1, 0).astype(dtype)
    positions = pos + jnp.zeros((B, 1), jnp.int32)

    def body(h, inp):
        lp, ck, cv, xk, xv = inp
        hn = layer_norm(h, lp["ln1"].astype(jnp.float32),
                        lp["ln1_b"].astype(jnp.float32), cfg.norm_eps)
        a, (nk, nv) = attn_sublayer(lp["self_attn"], hn, positions, cfg, ctx,
                                    kind="causal", cache=(ck, cv), pos=pos)
        h = h + a
        hn = layer_norm(h, lp["ln2"].astype(jnp.float32),
                        lp["ln2_b"].astype(jnp.float32), cfg.norm_eps)
        h = h + _cross_attend(lp["cross_attn"], hn, xk, xv, cfg, ctx)
        hn = layer_norm(h, lp["ln3"].astype(jnp.float32),
                        lp["ln3_b"].astype(jnp.float32), cfg.norm_eps)
        h = h + mlp_sublayer(lp["mlp"], hn, cfg, ctx)
        return h, (nk, nv)

    x, (nk, nv) = jax.lax.scan(
        body, x, (params["dec_blocks"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"]))
    x = layer_norm(x, params["final_norm"].astype(jnp.float32),
                   params["final_norm_b"].astype(jnp.float32), cfg.norm_eps)
    logits = lm_logits(params, x, cfg, ctx)
    new_cache = {**cache, "k": nk, "v": nv, "pos": pos + 1}
    return logits, new_cache
