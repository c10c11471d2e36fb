"""Decoder LMs: dense / MoE / VLM / SSM / hybrid families.

One forward implementation per family, all built from:
  * scan-over-layers with stacked parameters (HLO size independent of depth),
  * jax.checkpoint around the block body (remat),
  * optional per-layer ZeRO-3 parameter gathers through the HetCCL layer
    (explicit FSDP inside the scan body; adjoint = reduce-scatter),
  * logical-axis sharding constraints that work both inside the partially
    manual train shard_map and under fully-auto pjit serving.

Caches: decode carries a stacked KV cache (dense families), SSD + conv states
(ssm), or both (hybrid); prefill returns logits + a filled cache.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.collectives import fsdp_all_gather
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (ParamMeta, apply_rope, embed_lookup, is_meta,
                                 rms_norm, spec_tree)

# Names of the model's layers in the compiled program (DESIGN.md §16): each
# op a layer emits, forward, recompute and (through the transpose) backward,
# carries its scope in its HLO op_name, where a device trace finds it.  The
# DeepSeek-V3 expert layer adds its sub-scopes under ``mlp`` (repro.models.moe).
ATTENTION_SCOPE = "attention"
MLP_SCOPE = "mlp"
LM_HEAD_SCOPE = "lm_head"
EMBED_SCOPE = "embed"
# RMSNorm epsilon of MLA's compressed kv (HF DeepseekV3RMSNorm's default; the
# published config does not set it).
MLA_KV_NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Execution context: sharding rules + whether batch axes are manual."""

    rules: dict
    manual: bool                      # True inside the train shard_map
    dp_axes: tuple[str, ...] = ("pod", "data")

    def batch_axes(self):
        return None if self.manual else (
            self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0])

    def wsc(self, x, *axes):
        """with_sharding_constraint via logical axes ('batch'|'seq'|logical|None)."""
        parts = []
        for a in axes:
            if a == "batch":
                parts.append(self.batch_axes())
            elif a == "seq":
                parts.append("model" if self.rules.get("_attn_sp") else None)
            elif a in self.rules:
                parts.append(self.rules[a])
            else:
                parts.append(a)
        try:
            return jax.lax.with_sharding_constraint(x, P(*parts))
        except Exception:
            return x

    @property
    def fsdp(self) -> bool:
        return self.rules.get("_zero_stage", 1) >= 3 and self.manual


@dataclasses.dataclass(frozen=True)
class PlanLeaf:
    """Per-parameter plan: fsdp gather dim (or None) + auto-axis sharding.
    Deliberately NOT a pytree so it stays atomic under jax.tree.map."""

    dim: int | None
    spec: Any


def maybe_gather(layer_params, gather_plan):
    """ZeRO-3: all-gather this layer's shards over 'data' (HetCCL stage),
    then pin the result to its auto-axis (TP) sharding.

    The pin is essential: inside a partially-manual shard_map the auto-axis
    sharding of scan-carried parameters is NOT propagated into the loop body
    — without the constraint the SPMD partitioner silently replicates the
    weights over 'model' (measured: fully-gathered f32 expert weights on
    moonshot, EXPERIMENTS.md §Perf)."""
    def one(p, plan: PlanLeaf):
        if plan.dim is not None:
            p = fsdp_all_gather(p, "data", plan.dim)
        try:
            return jax.lax.with_sharding_constraint(p, plan.spec)
        except Exception:
            return p
    return jax.tree.map(one, layer_params, gather_plan)


def gather_plan_of(metas, rules, scanned: bool):
    """Per leaf: PlanLeaf(fsdp gather dim in the per-layer slice | None,
    auto-axis PartitionSpec of the gathered slice)."""
    specs = spec_tree(metas, rules)

    def one(m: ParamMeta, spec: P):
        dim = None
        auto_parts = []
        for i, ent in enumerate(spec):
            axes = (ent,) if isinstance(ent, str) else tuple(ent or ())
            if "data" in axes:
                dim = i - (1 if scanned else 0)
            kept = tuple(a for a in axes if a not in ("data", "pod"))
            auto_parts.append(kept[0] if len(kept) == 1 else (kept or None))
        if scanned:
            auto_parts = auto_parts[1:]
        return PlanLeaf(dim, P(*auto_parts))

    return jax.tree.map(one, metas, specs, is_leaf=is_meta)


# ---------------------------------------------------------------------------
# Parameter metadata
# ---------------------------------------------------------------------------

def _mla_metas(cfg: ModelConfig, pre: tuple, pax: tuple) -> dict:
    """Multi-head latent attention (DeepSeek-V2/V3, no q LoRA): W_q (D -> H x
    (nope + rope)), W_kv_a (D -> kv_lora_rank + rope), the latent's RMSNorm,
    W_kv_b (kv_lora_rank -> H x (nope + v)), W_o (H x v -> D)."""
    D, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim_
    return {
        "wq": ParamMeta(pre + (D, H, dn + dr), pax + ("embed", "q_heads", "head")),
        "wkv_a": ParamMeta(pre + (D, R + dr), pax + ("embed", "latent")),
        "kv_norm": ParamMeta(pre + (R,), pax + ("latent",), "ones"),
        "wkv_b": ParamMeta(pre + (R, H, dn + dv), pax + ("latent", "q_heads", "head")),
        "wo": ParamMeta(pre + (H, dv, D), pax + ("q_heads", "head", "embed")),
    }


def _attn_metas(cfg: ModelConfig, L_axis: str = "layers", L: int | None = None,
                bias: bool = False) -> dict:
    D, Hq, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    pre = (L,) if L else ()
    pax = (L_axis,) if L else ()
    if cfg.kv_lora_rank:
        return _mla_metas(cfg, pre, pax)
    m = {
        "wq": ParamMeta(pre + (D, Hq, hd), pax + ("embed", "q_heads", "head")),
        "wk": ParamMeta(pre + (D, Hkv, hd), pax + ("embed", "kv_heads", "head")),
        "wv": ParamMeta(pre + (D, Hkv, hd), pax + ("embed", "kv_heads", "head")),
        "wo": ParamMeta(pre + (Hq, hd, D), pax + ("q_heads", "head", "embed")),
    }
    if bias:
        m["bq"] = ParamMeta(pre + (Hq, hd), pax + ("q_heads", "head"), "zeros")
        m["bv"] = ParamMeta(pre + (Hkv, hd), pax + ("kv_heads", "head"), "zeros")
        m["bo"] = ParamMeta(pre + (D,), pax + ("embed",), "zeros")
    return m


def _mlp_metas(cfg: ModelConfig, L: int | None = None, gated: bool = True,
               bias: bool = False, L_axis: str = "layers",
               F: int | None = None) -> dict:
    D, F = cfg.d_model, F or cfg.d_ff
    pre = (L,) if L else ()
    pax = (L_axis,) if L else ()
    m = {
        "w1": ParamMeta(pre + (D, F), pax + ("embed", "mlp")),
        "w2": ParamMeta(pre + (F, D), pax + ("mlp", "embed")),
    }
    if gated:
        m["w3"] = ParamMeta(pre + (D, F), pax + ("embed", "mlp"))
    if bias:
        m["b1"] = ParamMeta(pre + (F,), pax + ("mlp",), "zeros")
        m["b2"] = ParamMeta(pre + (D,), pax + ("embed",), "zeros")
    return m


def _moe_metas(cfg: ModelConfig, L: int) -> dict:
    """The router over all ``n_experts``, the held experts' weights, and
    the shared experts as one SwiGLU."""
    D, E, F = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    Eh = cfg.experts_held
    m = {
        "router": ParamMeta((L, D, E), ("layers", "embed", "experts")),
        "w1": ParamMeta((L, Eh, D, F), ("layers", "experts", "embed", "expert_mlp")),
        "w3": ParamMeta((L, Eh, D, F), ("layers", "experts", "embed", "expert_mlp")),
        "w2": ParamMeta((L, Eh, F, D), ("layers", "experts", "expert_mlp", "embed")),
    }
    if cfg.n_shared_experts:
        m["shared"] = _mlp_metas(cfg, L=L, F=cfg.n_shared_experts * F)
    return m


def _ssm_metas(cfg: ModelConfig, L: int, L_axes: tuple[str, ...] = ("layers",)) -> dict:
    D, din = cfg.d_model, cfg.d_inner
    G, N, H, W = cfg.ssm_groups, cfg.ssm_state, cfg.n_ssm_heads, cfg.ssm_conv
    pre = (L,) if isinstance(L, int) else tuple(L)
    pax = L_axes
    return {
        "ln": ParamMeta(pre + (D,), pax + ("embed",), "ones"),
        "w_z": ParamMeta(pre + (D, din), pax + ("embed", "inner")),
        "w_x": ParamMeta(pre + (D, din), pax + ("embed", "inner")),
        "w_B": ParamMeta(pre + (D, G * N), pax + ("embed", "state")),
        "w_C": ParamMeta(pre + (D, G * N), pax + ("embed", "state")),
        "w_dt": ParamMeta(pre + (D, H), pax + ("embed", "ssm_heads")),
        "conv_x": ParamMeta(pre + (W, din), pax + ("conv", "inner"), "normal", 0.5),
        "conv_B": ParamMeta(pre + (W, G * N), pax + ("conv", "state"), "normal", 0.5),
        "conv_C": ParamMeta(pre + (W, G * N), pax + ("conv", "state"), "normal", 0.5),
        "A_log": ParamMeta(pre + (H,), pax + ("ssm_heads",), "zeros"),
        "dt_bias": ParamMeta(pre + (H,), pax + ("ssm_heads",), "zeros"),
        "D": ParamMeta(pre + (H,), pax + ("ssm_heads",), "ones"),
        "gnorm": ParamMeta(pre + (din,), pax + ("inner",), "ones"),
        "out_proj": ParamMeta(pre + (din, D), pax + ("inner", "embed")),
    }


def abstract_params(cfg: ModelConfig) -> dict:
    """Meta tree for every decoder family.  Vocab dims use padded_vocab
    (multiple of 128) so the head shards over any TP degree."""
    D, V, L = cfg.d_model, cfg.padded_vocab, cfg.n_layers
    base = {
        "embed": ParamMeta((V, D), ("vocab", "embed"), "normal", 0.02),
        "final_norm": ParamMeta((D,), ("embed",), "ones"),
        "lm_head": ParamMeta((D, V), ("embed", "vocab")),
    }
    if cfg.family in ("dense", "vlm"):
        base["blocks"] = {
            "ln1": ParamMeta((L, D), ("layers", "embed"), "ones"),
            "ln2": ParamMeta((L, D), ("layers", "embed"), "ones"),
            "attn": _attn_metas(cfg, L=L),
            "mlp": _mlp_metas(cfg, L=L),
        }
    elif cfg.family == "moe":
        Lm = L - cfg.first_k_dense
        base["blocks"] = {
            "ln1": ParamMeta((Lm, D), ("layers", "embed"), "ones"),
            "ln2": ParamMeta((Lm, D), ("layers", "embed"), "ones"),
            "attn": _attn_metas(cfg, L=Lm),
            "moe": _moe_metas(cfg, Lm),
        }
        if cfg.first_k_dense:      # leading dense layers, unstacked
            base["dense"] = {str(i): {
                "ln1": ParamMeta((D,), ("embed",), "ones"),
                "ln2": ParamMeta((D,), ("embed",), "ones"),
                "attn": _attn_metas(cfg),
                "mlp": _mlp_metas(cfg, F=cfg.d_ff_dense),
            } for i in range(cfg.first_k_dense)}
    elif cfg.family == "ssm":
        base["blocks"] = _ssm_metas(cfg, L)
    elif cfg.family == "hybrid":
        k = cfg.attn_every
        n_groups, leftover = L // k, L % k
        base["groups"] = _ssm_metas(cfg, (n_groups, k), ("group", "layers"))
        if leftover:
            base["tail"] = _ssm_metas(cfg, leftover)
        base["shared"] = {
            "ln1": ParamMeta((D,), ("embed",), "ones"),
            "ln2": ParamMeta((D,), ("embed",), "ones"),
            "attn": _attn_metas(cfg),
            "mlp": _mlp_metas(cfg),
        }
    else:
        raise ValueError(cfg.family)
    return base


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _mla_qkv(p, x, positions, cfg: ModelConfig):
    """MLA's q, k, v, computed unabsorbed: q (B,S,H,nope+rope) from W_q;
    the latent ``c`` and the one-head ``k_pe`` from W_kv_a; ``c`` normed,
    then k_nope and v from W_kv_b; RoPE on q_pe and k_pe; k_pe broadcast
    over the heads."""
    dn, R = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    ckv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    c = rms_norm(ckv[..., :R], p["kv_norm"].astype(jnp.float32), MLA_KV_NORM_EPS)
    kv = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"].astype(x.dtype))
    q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    k_pe = apply_rope(ckv[..., None, R:], positions, cfg.rope_theta)
    H = q.shape[2]
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe, k_pe.shape[:2] + (H, k_pe.shape[-1]))], axis=-1)
    return q, k, kv[..., dn:]


def _qkv(p, x, positions, cfg: ModelConfig, ctx: Ctx):
    if "wkv_a" in p:
        return _mla_qkv(p, x, positions, cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.family != "encdec":                       # whisper has no RoPE
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return q, k, v


def attn_sublayer(p, h, positions, cfg: ModelConfig, ctx: Ctx, *,
                  kind="causal", cache=None, pos=None):
    """Attention over pre-normed input ``h``.  Returns (output, new_cache).

    cache: (k, v) buffers for decode; pos: current cache length (scalar).
    """
    with jax.named_scope(ATTENTION_SCOPE):
        q, k, v = _qkv(p, h, positions, cfg, ctx)
        q = ctx.wsc(q, "batch", "seq", "q_heads", None)
        new_cache = None
        if cache is None:
            out = attn_mod.attention(q, k, v, kind=kind, window=cfg.window,
                                     chunk=cfg.attn_chunk)
        else:
            ck, cv = cache
            if cfg.window and ck.shape[1] == cfg.window:
                ck, cv = attn_mod.window_cache_update(ck, cv, k, v, pos)
                out = attn_mod.window_decode_attention(q, ck, cv, pos,
                                                       cfg.window)
            else:
                ck, cv = attn_mod.cache_update(ck, cv, k, v, pos)
                out = attn_mod.attention(q, ck, cv, kind=kind,
                                         window=cfg.window, q_offset=pos,
                                         k_len=pos + q.shape[1],
                                         chunk=cfg.attn_chunk)
            new_cache = (ck, cv)
        proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(h.dtype))
        if "bo" in p:
            proj = proj + p["bo"].astype(h.dtype)
        return proj, new_cache


def mlp_sublayer(p, h, cfg: ModelConfig, ctx: Ctx):
    """FFN over pre-normed input.  Gated-SiLU if w3 present, else GELU."""
    with jax.named_scope(MLP_SCOPE):
        h1 = jnp.einsum("bsd,df->bsf", h, p["w1"].astype(h.dtype))
        if "b1" in p:
            h1 = h1 + p["b1"].astype(h.dtype)
        if "w3" in p:
            h3 = jnp.einsum("bsd,df->bsf", h, p["w3"].astype(h.dtype))
            hh = jax.nn.silu(h1.astype(jnp.float32)).astype(h.dtype) * h3
        else:
            hh = jax.nn.gelu(h1.astype(jnp.float32)).astype(h.dtype)
        out = jnp.einsum("bsf,fd->bsd", hh, p["w2"].astype(h.dtype))
        if "b2" in p:
            out = out + p["b2"].astype(h.dtype)
        return out


def ffn_sublayer(p, h2, cfg: ModelConfig, ctx: Ctx):
    """The block's feed-forward over pre-normed ``h2``: the dense MLP, or
    the expert layer.  Returns (output, aux)."""
    if "moe" not in p:
        return mlp_sublayer(p["mlp"], h2, cfg, ctx), {}
    B, S, D = h2.shape
    # Resolve any pending partial-sum sharding BEFORE dispatch: without
    # this XLA defers the attention-output psum past the token gather and
    # all-reduces the top_k-times-larger (T*k, D) matrix (measured 6x
    # wire inflation on moonshot — see EXPERIMENTS.md §Perf).
    if not ctx.rules.get("_attn_sp"):
        h2 = ctx.wsc(h2, "batch", None, None)
    if cfg.router == "sigmoid":
        with jax.named_scope(MLP_SCOPE):
            out, aux = moe_mod.deepseek_moe(
                h2.reshape(B * S, D), p["moe"], n_seqs=B, top_k=cfg.top_k,
                expert_offset=cfg.expert_offset, n_held=cfg.experts_held,
                routed_scaling=cfg.routed_scaling, norm_topk=cfg.norm_topk,
                seq_aux_alpha=cfg.seq_aux_alpha)
        return out.reshape(B, S, D), aux
    # Buffer-replication pins are a train-context (manual DP) move only:
    # under pjit serving the token dim is batch-sharded over (pod, data)
    # and pinning the dispatch buffer replicated would gather the whole
    # batch across the fleet (measured 3x prefill regression).
    out, aux = moe_mod.moe_ffn(h2.reshape(B * S, D), p["moe"],
                               n_experts=cfg.n_experts, top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor,
                               expert_axis=ctx.rules.get("experts"),
                               replicate_buffers=ctx.manual)
    return out.reshape(B, S, D), aux


def dense_block(p, x, positions, cfg, ctx, cache=None, pos=None):
    h = rms_norm(x, p["ln1"].astype(jnp.float32), cfg.norm_eps)
    a, new_cache = attn_sublayer(p["attn"], h, positions, cfg, ctx,
                                 cache=cache, pos=pos)
    x = x + a
    h2 = rms_norm(x, p["ln2"].astype(jnp.float32), cfg.norm_eps)
    out, aux = ffn_sublayer(p, h2, cfg, ctx)
    return x + out, new_cache, aux


def _aux_loss(a: dict):
    """One layer's auxiliary loss: the softmax router's weighted switch and
    z losses, or the sigmoid router's balance loss."""
    return (a.get("moe_aux", 0.0) * 0.01 + a.get("moe_z", 0.0) * 1e-3
            + a.get("moe_balance", 0.0))


def ssm_block(p, x, cfg: ModelConfig, ctx: Ctx, state=None, conv=None):
    """Mamba2 block.  state: (B,H,N,P) + conv states for decode, else None."""
    h = rms_norm(x, p["ln"].astype(jnp.float32), cfg.norm_eps)
    z = jnp.einsum("bsd,de->bse", h, p["w_z"].astype(x.dtype))
    xin = jnp.einsum("bsd,de->bse", h, p["w_x"].astype(x.dtype))
    Bp = jnp.einsum("bsd,de->bse", h, p["w_B"].astype(x.dtype))
    Cp = jnp.einsum("bsd,de->bse", h, p["w_C"].astype(x.dtype))
    dt = jnp.einsum("bsd,de->bse", h, p["w_dt"].astype(x.dtype))
    B_, S, _ = x.shape
    H, Pd = cfg.n_ssm_heads, cfg.ssm_headdim
    G, N = cfg.ssm_groups, cfg.ssm_state
    new_state = None
    if state is None:
        xin = jax.nn.silu(ssm_mod.causal_conv1d(xin, p["conv_x"]).astype(jnp.float32)).astype(x.dtype)
        Bp = jax.nn.silu(ssm_mod.causal_conv1d(Bp, p["conv_B"]).astype(jnp.float32)).astype(x.dtype)
        Cp = jax.nn.silu(ssm_mod.causal_conv1d(Cp, p["conv_C"]).astype(jnp.float32)).astype(x.dtype)
    else:
        sst, cx, cB, cC = state["s"], conv["x"], conv["B"], conv["C"]
        xin_y, cx = ssm_mod.conv_decode_step(cx, xin, p["conv_x"])
        Bp_y, cB = ssm_mod.conv_decode_step(cB, Bp, p["conv_B"])
        Cp_y, cC = ssm_mod.conv_decode_step(cC, Cp, p["conv_C"])
        xin = jax.nn.silu(xin_y.astype(jnp.float32)).astype(x.dtype)
        Bp = jax.nn.silu(Bp_y.astype(jnp.float32)).astype(x.dtype)
        Cp = jax.nn.silu(Cp_y.astype(jnp.float32)).astype(x.dtype)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xin.reshape(B_, S, H, Pd)
    Bh = Bp.reshape(B_, S, G, N)
    Ch = Cp.reshape(B_, S, G, N)
    if state is None:
        y, _ = ssm_mod.ssd_scan(xh, dt, A, Bh, Ch, p["D"], cfg.ssm_chunk)
    else:
        y, s_new = ssm_mod.ssd_decode_step(sst, xh, dt, A, Bh, Ch, p["D"])
        new_state = ({"s": s_new}, {"x": cx, "B": cB, "C": cC})
    y = y.reshape(B_, S, cfg.d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 p["gnorm"].astype(jnp.float32), cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))
    return x + out, new_state


def ssm_prefill_block(p, x, cfg, ctx):
    """SSM block that also returns final (ssd, conv) states for decoding."""
    h = rms_norm(x, p["ln"].astype(jnp.float32), cfg.norm_eps)
    z = jnp.einsum("bsd,de->bse", h, p["w_z"].astype(x.dtype))
    xin0 = jnp.einsum("bsd,de->bse", h, p["w_x"].astype(x.dtype))
    Bp0 = jnp.einsum("bsd,de->bse", h, p["w_B"].astype(x.dtype))
    Cp0 = jnp.einsum("bsd,de->bse", h, p["w_C"].astype(x.dtype))
    dt = jnp.einsum("bsd,de->bse", h, p["w_dt"].astype(x.dtype))
    W = cfg.ssm_conv
    conv_states = {"x": xin0[:, -(W - 1):], "B": Bp0[:, -(W - 1):], "C": Cp0[:, -(W - 1):]}
    xin = jax.nn.silu(ssm_mod.causal_conv1d(xin0, p["conv_x"]).astype(jnp.float32)).astype(x.dtype)
    Bp = jax.nn.silu(ssm_mod.causal_conv1d(Bp0, p["conv_B"]).astype(jnp.float32)).astype(x.dtype)
    Cp = jax.nn.silu(ssm_mod.causal_conv1d(Cp0, p["conv_C"]).astype(jnp.float32)).astype(x.dtype)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    B_, S, _ = x.shape
    H, Pd, G, N = cfg.n_ssm_heads, cfg.ssm_headdim, cfg.ssm_groups, cfg.ssm_state
    y, final_state = ssm_mod.ssd_scan(xin.reshape(B_, S, H, Pd), dt, A,
                                      Bp.reshape(B_, S, G, N),
                                      Cp.reshape(B_, S, G, N), p["D"], cfg.ssm_chunk)
    y = y.reshape(B_, S, cfg.d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 p["gnorm"].astype(jnp.float32), cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))
    return x + out, {"s": final_state}, conv_states


# ---------------------------------------------------------------------------
# Whole-model forwards (scan over layers)
# ---------------------------------------------------------------------------

def _positions_for(cfg: ModelConfig, tokens, offset=0, mrope=None):
    if cfg.mrope_sections:
        if mrope is not None:
            return mrope
        B, S = tokens.shape
        p = offset + jnp.arange(S)[None, :] + jnp.zeros((B, 1), jnp.int32)
        return jnp.broadcast_to(p[None], (3,) + p.shape)      # text-only default
    B, S = tokens.shape
    return offset + jnp.arange(S)[None, :] + jnp.zeros((B, 1), jnp.int32)


def _blocks_gplan(cfg: ModelConfig, rules):
    metas = abstract_params(cfg)
    out = {}
    for key in ("blocks", "groups", "tail"):
        if key in metas:
            out[key] = gather_plan_of(metas[key], rules, scanned=True)
    if "shared" in metas:
        out["shared"] = gather_plan_of(metas["shared"], rules, scanned=False)
    return out


def forward_lm(params, tokens, cfg: ModelConfig, ctx: Ctx, *, mrope=None,
               return_kv: bool = False):
    """Token ids -> (final hidden states (B,S,D), aux): aux["loss"] is the
    auxiliary loss per token, beside it the expert layer's counters.

    Families: dense | moe | vlm (dense_block), ssm (ssm_block),
    hybrid (grouped ssm + shared attention).
    """
    positions = _positions_for(cfg, tokens, mrope=mrope)
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope(EMBED_SCOPE):
        x = embed_lookup(params["embed"], tokens).astype(dtype)
    x = ctx.wsc(x, "batch", "seq", None)
    gplans = _blocks_gplan(cfg, ctx.rules) if ctx.manual else None

    if cfg.family in ("dense", "moe", "vlm"):
        def layer(carry, layer_p):
            # the expert counters leave as scan outputs, not through the
            # differentiated carry
            h, aux = carry
            h, _, a = dense_block(layer_p, h, positions, cfg, ctx)
            return (h, aux + _aux_loss(a)), {k: a[k] for k in moe_mod.COUNTERS if k in a}

        def body_simple(carry, layer_p):
            if gplans is not None:
                layer_p = maybe_gather(layer_p, gplans["blocks"])
            return layer(carry, layer_p)
        carry = (x, jnp.zeros((), jnp.float32))
        # the leading dense layers, unstacked (top-level params: ZeRO-3
        # gathers them whole with the embedding and the head)
        for i in range(cfg.first_k_dense):
            carry, _ = jax.checkpoint(layer)(carry, params["dense"][str(i)])
        (x, aux_loss), counters = jax.lax.scan(jax.checkpoint(body_simple), carry,
                                               params["blocks"])
        aux = {"loss": aux_loss, **moe_mod.reduce_counters(counters)}
    elif cfg.family == "ssm":
        def body(h, layer_p):
            if gplans is not None:
                layer_p = maybe_gather(layer_p, gplans["blocks"])
            h, _ = ssm_block(layer_p, h, cfg, ctx)
            return h, None
        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
        aux = {"loss": jnp.zeros((), jnp.float32)}
    elif cfg.family == "hybrid":
        shared = params["shared"]
        if gplans is not None:
            shared = maybe_gather(shared, gplans["shared"])

        def inner(h, lp):
            h, _ = ssm_block(lp, h, cfg, ctx)
            return h, None

        def group_body(h, group_p):
            if gplans is not None:
                group_p = maybe_gather(group_p, gplans["groups"])
            h, _ = jax.lax.scan(inner, h, group_p)
            hn = rms_norm(h, shared["ln1"].astype(jnp.float32), cfg.norm_eps)
            a, _ = attn_sublayer(shared["attn"], hn, positions, cfg, ctx)
            h = h + a
            h2 = rms_norm(h, shared["ln2"].astype(jnp.float32), cfg.norm_eps)
            h = h + mlp_sublayer(shared["mlp"], h2, cfg, ctx)
            return h, None

        x, _ = jax.lax.scan(jax.checkpoint(group_body), x, params["groups"])
        if "tail" in params:
            tail_p = params["tail"]
            if gplans is not None and "tail" in gplans:
                def tail_body(h, lp):
                    lp = maybe_gather(lp, gplans["tail"])
                    h, _ = ssm_block(lp, h, cfg, ctx)
                    return h, None
            else:
                def tail_body(h, lp):
                    h, _ = ssm_block(lp, h, cfg, ctx)
                    return h, None
            x, _ = jax.lax.scan(jax.checkpoint(tail_body), x, tail_p)
        aux = {"loss": jnp.zeros((), jnp.float32)}
    else:
        raise ValueError(cfg.family)

    with jax.named_scope(LM_HEAD_SCOPE):
        x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
    return x, aux


def lm_loss_from_hidden(params, x, labels, mask, cfg: ModelConfig, ctx: Ctx):
    """Chunked cross-entropy.  Returns (sum of token losses, token count)."""
    with jax.named_scope(LM_HEAD_SCOPE):
        B, S, D = x.shape
        T = B * S
        xf = x.reshape(T, D)
        lf = labels.reshape(T)
        mf = mask.reshape(T).astype(jnp.float32)
        chunk = min(cfg.loss_chunk, T)
        n = -(-T // chunk)
        padT = n * chunk - T
        if padT:
            xf = jnp.pad(xf, ((0, padT), (0, 0)))
            lf = jnp.pad(lf, (0, padT))
            mf = jnp.pad(mf, (0, padT))
        xc = xf.reshape(n, chunk, D)
        lc = lf.reshape(n, chunk)
        mc = mf.reshape(n, chunk)
        head = params["lm_head"]

        pad_mask = (jnp.arange(cfg.padded_vocab) >= cfg.vocab)

        def body(acc, inp):
            xs, ls, ms = inp
            logits = (xs @ head.astype(xs.dtype)).astype(jnp.float32)
            logits = ctx.wsc(logits, None, "vocab")
            logits = jnp.where(pad_mask[None, :], -1e30, logits)  # vocab pad
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, ls[:, None], axis=-1)[:, 0]
            return acc + jnp.sum((lse - gold) * ms), None

        loss_sum, _ = jax.lax.scan(jax.checkpoint(body),
                                   jnp.zeros((), jnp.float32), (xc, lc, mc))
        return loss_sum, jnp.sum(mf)


def lm_logits(params, x, cfg: ModelConfig, ctx: Ctx):
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    if cfg.padded_vocab != cfg.vocab:                  # mask the vocab pad
        logits = jnp.where(jnp.arange(cfg.padded_vocab) >= cfg.vocab,
                           jnp.asarray(-1e30, logits.dtype), logits)
    return ctx.wsc(logits, "batch", None, "vocab")


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def cache_metas(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Meta tree for the decode cache (ParamMeta reused: shape + logical axes)."""
    hd = cfg.head_dim_
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        S = min(max_len, cfg.window) if cfg.window else max_len

        def kv(n, width):
            return ParamMeta((n, batch, S, cfg.n_kv_heads, width),
                             ("layers", "cbatch", "cseq", "kv_heads", "head"), "zeros")
        n = cfg.n_layers - cfg.first_k_dense
        c = {"k": kv(n, hd), "v": kv(n, cfg.v_head_dim_),
             "pos": ParamMeta((), (), "zeros")}
        if cfg.first_k_dense:      # the leading dense layers' own
            c["dense_k"] = kv(cfg.first_k_dense, hd)
            c["dense_v"] = kv(cfg.first_k_dense, cfg.v_head_dim_)
        if cfg.family == "encdec":
            c["cross_k"] = ParamMeta(
                (cfg.n_layers, batch, cfg.n_frames, cfg.n_kv_heads, hd),
                ("layers", "cbatch", "frames", "kv_heads", "head"), "zeros")
            c["cross_v"] = ParamMeta(
                (cfg.n_layers, batch, cfg.n_frames, cfg.n_kv_heads, hd),
                ("layers", "cbatch", "frames", "kv_heads", "head"), "zeros")
        return c
    H, Pd, N, W = cfg.n_ssm_heads, cfg.ssm_headdim, cfg.ssm_state, cfg.ssm_conv
    G = cfg.ssm_groups
    din = cfg.d_inner
    def ssm_state_metas(pre, pax):
        return {
            "s": ParamMeta(pre + (batch, H, N, Pd),
                           pax + ("cbatch", "ssm_heads", "state", "head"), "zeros"),
            "conv_x": ParamMeta(pre + (batch, W - 1, din),
                                pax + ("cbatch", "conv", "inner"), "zeros"),
            "conv_B": ParamMeta(pre + (batch, W - 1, G * N),
                                pax + ("cbatch", "conv", "state"), "zeros"),
            "conv_C": ParamMeta(pre + (batch, W - 1, G * N),
                                pax + ("cbatch", "conv", "state"), "zeros"),
        }
    if cfg.family == "ssm":
        return {**ssm_state_metas((cfg.n_layers,), ("layers",)),
                "pos": ParamMeta((), (), "zeros")}
    if cfg.family == "hybrid":
        k = cfg.attn_every
        n_groups, leftover = cfg.n_layers // k, cfg.n_layers % k
        out = {"groups": ssm_state_metas((n_groups, k), ("group", "layers")),
               "shared_k": ParamMeta((n_groups, batch, max_len, cfg.n_kv_heads, hd),
                                     ("group", "cbatch", "cseq", "kv_heads", "head"), "zeros"),
               "shared_v": ParamMeta((n_groups, batch, max_len, cfg.n_kv_heads, hd),
                                     ("group", "cbatch", "cseq", "kv_heads", "head"), "zeros"),
               "pos": ParamMeta((), (), "zeros")}
        if leftover:
            out["tail"] = ssm_state_metas((leftover,), ("layers",))
        return out
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Decode / prefill
# ---------------------------------------------------------------------------

def decode_lm(params, cache, tokens, cfg: ModelConfig, ctx: Ctx):
    """One decode step.  tokens (B,1) -> (logits (B,1,V), new cache)."""
    pos = cache["pos"].astype(jnp.int32)
    positions = _positions_for(cfg, tokens, offset=pos)
    dtype = jnp.dtype(cfg.dtype)
    x = embed_lookup(params["embed"], tokens).astype(dtype)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(h, inp):
            layer_p, ck, cv = inp
            h, new_c, _ = dense_block(layer_p, h, positions, cfg, ctx,
                                      cache=(ck, cv), pos=pos)
            return h, new_c
        new_cache = {"pos": pos + 1}
        if cfg.first_k_dense:
            dense = []
            for i in range(cfg.first_k_dense):
                x, kv = body(x, (params["dense"][str(i)], cache["dense_k"][i],
                                 cache["dense_v"][i]))
                dense.append(kv)
            new_cache["dense_k"] = jnp.stack([kv[0] for kv in dense])
            new_cache["dense_v"] = jnp.stack([kv[1] for kv in dense])
        x, (nk, nv) = jax.lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
        new_cache.update(k=nk, v=nv)
    elif cfg.family == "ssm":
        def body(h, inp):
            layer_p, st = inp
            h, (s_new, conv_new) = ssm_block(
                layer_p, h, cfg, ctx,
                state={"s": st["s"]},
                conv={"x": st["conv_x"], "B": st["conv_B"], "C": st["conv_C"]})
            return h, {"s": s_new["s"], "conv_x": conv_new["x"],
                       "conv_B": conv_new["B"], "conv_C": conv_new["C"]}
        st_in = {k: cache[k] for k in ("s", "conv_x", "conv_B", "conv_C")}
        x, st_out = jax.lax.scan(body, x, (params["blocks"], st_in))
        new_cache = {**st_out, "pos": pos + 1}
    elif cfg.family == "hybrid":
        shared = params["shared"]

        def inner(h, inp):
            lp, st = inp
            h, (s_new, conv_new) = ssm_block(
                lp, h, cfg, ctx, state={"s": st["s"]},
                conv={"x": st["conv_x"], "B": st["conv_B"], "C": st["conv_C"]})
            return h, {"s": s_new["s"], "conv_x": conv_new["x"],
                       "conv_B": conv_new["B"], "conv_C": conv_new["C"]}

        def group_body(h, inp):
            gp, gst, ck, cv = inp
            h, gst_new = jax.lax.scan(inner, h, (gp, gst))
            hn = rms_norm(h, shared["ln1"].astype(jnp.float32), cfg.norm_eps)
            a, (nk, nv) = attn_sublayer(shared["attn"], hn, positions, cfg, ctx,
                                        cache=(ck, cv), pos=pos)
            h = h + a
            h2 = rms_norm(h, shared["ln2"].astype(jnp.float32), cfg.norm_eps)
            h = h + mlp_sublayer(shared["mlp"], h2, cfg, ctx)
            return h, (gst_new, nk, nv)

        x, (gst, nk, nv) = jax.lax.scan(
            group_body, x,
            (params["groups"], cache["groups"], cache["shared_k"], cache["shared_v"]))
        new_cache = {"groups": gst, "shared_k": nk, "shared_v": nv, "pos": pos + 1}
        if "tail" in params:
            x, tail_st = jax.lax.scan(inner, x, (params["tail"], cache["tail"]))
            new_cache["tail"] = tail_st
    else:
        raise ValueError(cfg.family)

    x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
    logits = lm_logits(params, x, cfg, ctx)
    return logits, new_cache


def prefill_lm(params, tokens, cfg: ModelConfig, ctx: Ctx, *, mrope=None,
               max_len: int | None = None):
    """Prefill: forward over the prompt, returning last-position logits + a
    cache of capacity ``max_len`` (>= S) positioned at S, ready for decode."""
    B, S = tokens.shape
    max_len = max(max_len or S, S)
    positions = _positions_for(cfg, tokens, mrope=mrope)
    dtype = jnp.dtype(cfg.dtype)
    x = embed_lookup(params["embed"], tokens).astype(dtype)
    x = ctx.wsc(x, "batch", "seq", None)
    pos0 = jnp.zeros((), jnp.int32)

    if cfg.family in ("dense", "moe", "vlm"):
        Sc = min(S, cfg.window) if cfg.window else S
        zk = jnp.zeros((B, Sc, cfg.n_kv_heads, cfg.head_dim_), dtype)

        def body(h, layer_p):
            hn = rms_norm(h, layer_p["ln1"].astype(jnp.float32), cfg.norm_eps)
            q, k, v = _qkv(layer_p["attn"], hn, positions, cfg, ctx)
            out = attn_mod.attention(q, k, v, kind="causal", window=cfg.window,
                                     chunk=cfg.attn_chunk)
            a = jnp.einsum("bshk,hkd->bsd", out, layer_p["attn"]["wo"].astype(h.dtype))
            h = h + a
            h2 = rms_norm(h, layer_p["ln2"].astype(jnp.float32), cfg.norm_eps)
            h = h + ffn_sublayer(layer_p, h2, cfg, ctx)[0]
            if cfg.window and Sc == cfg.window:
                # rolling cache: scatter last W positions at slot = pos % W
                last = jnp.arange(S - Sc, S)
                ck = zk.at[:, last % Sc].set(k[:, -Sc:].astype(dtype))
                cv = zk.at[:, last % Sc].set(v[:, -Sc:].astype(dtype))
            else:
                pad = max_len - S
                widths = ((0, 0), (0, pad), (0, 0), (0, 0))
                ck = jnp.pad(k.astype(dtype), widths)
                cv = jnp.pad(v.astype(dtype), widths)
            return h, (ck, cv)

        cache = {"pos": jnp.asarray(S, jnp.int32)}
        if cfg.first_k_dense:
            dense = []
            for i in range(cfg.first_k_dense):
                x, kv = body(x, params["dense"][str(i)])
                dense.append(kv)
            cache["dense_k"] = jnp.stack([kv[0] for kv in dense])
            cache["dense_v"] = jnp.stack([kv[1] for kv in dense])
        x, (ks, vs) = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
        cache.update(k=ks, v=vs)
    elif cfg.family == "ssm":
        def body(h, layer_p):
            h, s, conv = ssm_prefill_block(layer_p, h, cfg, ctx)
            return h, {"s": s["s"], "conv_x": conv["x"], "conv_B": conv["B"],
                       "conv_C": conv["C"]}
        x, st = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
        cache = {**st, "pos": jnp.asarray(S, jnp.int32)}
    elif cfg.family == "hybrid":
        shared = params["shared"]

        def inner(h, lp):
            h, s, conv = ssm_prefill_block(lp, h, cfg, ctx)
            return h, {"s": s["s"], "conv_x": conv["x"], "conv_B": conv["B"],
                       "conv_C": conv["C"]}

        def group_body(h, gp):
            h, gst = jax.lax.scan(inner, h, gp)
            hn = rms_norm(h, shared["ln1"].astype(jnp.float32), cfg.norm_eps)
            q, k, v = _qkv(shared["attn"], hn, positions, cfg, ctx)
            out = attn_mod.attention(q, k, v, kind="causal", chunk=cfg.attn_chunk)
            h = h + jnp.einsum("bshk,hkd->bsd", out, shared["attn"]["wo"].astype(h.dtype))
            h2 = rms_norm(h, shared["ln2"].astype(jnp.float32), cfg.norm_eps)
            h = h + mlp_sublayer(shared["mlp"], h2, cfg, ctx)
            widths = ((0, 0), (0, max_len - S), (0, 0), (0, 0))
            return h, (gst, jnp.pad(k.astype(dtype), widths),
                       jnp.pad(v.astype(dtype), widths))

        x, (gst, ks, vs) = jax.lax.scan(jax.checkpoint(group_body), x, params["groups"])
        cache = {"groups": gst, "shared_k": ks, "shared_v": vs,
                 "pos": jnp.asarray(S, jnp.int32)}
        if "tail" in params:
            x, tail_st = jax.lax.scan(jax.checkpoint(inner), x, params["tail"])
            cache["tail"] = tail_st
    else:
        raise ValueError(cfg.family)

    x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
    logits = lm_logits(params, x[:, -1:], cfg, ctx)
    return logits, cache
