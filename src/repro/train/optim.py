"""AdamW with ZeRO-style state partitioning (paper §5.3, Appendix D.4).

The paper trains with DeepSpeed ZeRO-1 and ZeRO-3; both are implemented here
by hand, with every data-parallel collective issued through the HetCCL layer:

  ZeRO-1: params replicated across DP; f32 master + m + v are *flat shards* —
          each DP rank owns 1/W of every tensor.  Per step:
          grads -> HetCCL tree_all_reduce (bucketed; pipelined
          reduce-scatter -> all-gather across buckets, hierarchical or
          multi-channel-pipelined across pods per the installed mode) ->
          local shard update -> HetCCL AllGather of updated params.
          (Table 3: "All-Gather (OS), All-Reduce (G)")
  ZeRO-3: params themselves sharded over 'data' (gathered per layer inside
          the forward scan via fsdp_all_gather, whose adjoint reduce-scatters
          the gradients); optimizer state is shard-shaped; only the cross-pod
          gradient stage remains, a HetCCL ring.
          (Table 3: "All-Gather (P), Reduce-Scatter (G)")

Everything in this module runs *inside* the train shard_map (manual
'pod'/'data' axes).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import RunConfig
from repro.core import hetccl
from repro.kernels import quant

# Name of the optimizer step in the compiled program (DESIGN.md §16): clip,
# global norm, AdamW and the shard slice carry it in their HLO op_name; the
# collectives issued from inside keep their own ``hetccl.*`` scopes below it.
SCOPE = "optimizer"


def ef_codec(rc: RunConfig) -> str | None:
    """The wire codec error feedback compensates for, or None when EF is off
    (DESIGN.md §17).

    ``rc.error_feedback``: "auto" enables EF iff the gradient reductions
    actually quantize — a ``wire_quant`` codec on the large class of
    reduce_scatter/all_reduce after the run-level ``rc.wire_quant`` knob
    composes into the table (planner rows win, ``with_wire_quant``);
    "on" additionally *requires* a codec to resolve; "off" disables EF —
    the convergence ablation (quantize without compensation).
    """
    if rc.error_feedback not in ("auto", "on", "off"):
        raise ValueError(
            f"unknown error_feedback {rc.error_feedback!r}; "
            f"expected 'auto', 'on' or 'off'")
    if rc.error_feedback == "off":
        return None
    codec = None
    if rc.policies is not None:
        table = rc.policies.with_wire_quant(rc.wire_quant)
        for op in ("reduce_scatter", "all_reduce"):
            p = table.lookup(op, "large")
            if p.backend == "pallas" and p.wire_quant:
                codec = p.wire_quant
                break
    elif rc.wire_quant and rc.backend == "pallas":
        codec = rc.wire_quant
    if codec is None and rc.error_feedback == "on":
        raise ValueError(
            "error_feedback='on' but no wire_quant codec resolves: set "
            "RunConfig.wire_quant (with backend='pallas') or plan a policy "
            "table with quantized gradient rows")
    return codec


def ef_init(params):
    """Rank-local EF residual state: one flat f32 array per param leaf,
    zero-initialized, in the *local* gradient size (full leaf under ZeRO-1,
    'data'-shard under ZeRO-3).  Error feedback is worker-local — the
    residual leaf is sharded over the full DP axes so every rank keeps its
    own quantization error (the ``"ef"`` opt-state entry, DESIGN.md §17)."""
    return jax.tree.map(lambda p: jnp.zeros((p.size,), jnp.float32), params)


def ef_apply(grads, residuals, codec: str):
    """Per-leaf error-feedback compression before the quantized collective
    (DESIGN.md §17): each local contribution is projected onto the codec's
    grid via :func:`repro.kernels.quant.ef_compress` — the ring's first-hop
    quantization of an on-grid value is then exact (the idempotence
    property) — and the projection error telescopes into the rank-local
    residual instead of compounding across steps.

    Returns ``(compressed_grads, new_residuals)``.
    """
    def one(g, r):
        c, nr = quant.ef_compress(g.astype(jnp.float32).reshape(-1), r,
                                  codec=codec)
        return c.reshape(g.shape), nr

    pairs = jax.tree.map(one, grads, residuals)
    is_pair = lambda x: isinstance(x, tuple)
    return (jax.tree.map(lambda t: t[0], pairs, is_leaf=is_pair),
            jax.tree.map(lambda t: t[1], pairs, is_leaf=is_pair))


def dp_rank_and_world(dp_axes: tuple[str, ...]) -> tuple[jax.Array, int]:
    """Flat DP rank and world size inside shard_map.

    ``dp_axes`` must be pod-major (('pod','data')) so the rank enumeration
    matches HetCCL's all_gather concatenation order.
    """
    rank = jnp.zeros((), jnp.int32)
    world = 1
    for a in dp_axes:
        n = lax.axis_size(a)
        rank = rank * n + lax.axis_index(a)
        world *= n
    return rank, world


def _pad_len(n: int, w: int) -> int:
    return -(-n // w) * w


def adam_update(g, m, v, master, step, rc: RunConfig, decay_mask=1.0):
    """One AdamW update in f32.  All args shard-shaped."""
    g = g.astype(jnp.float32)
    m = rc.beta1 * m + (1 - rc.beta1) * g
    v = rc.beta2 * v + (1 - rc.beta2) * g * g
    t = step.astype(jnp.float32) + 1.0
    mhat = m / (1 - rc.beta1 ** t)
    vhat = v / (1 - rc.beta2 ** t)
    upd = mhat / (jnp.sqrt(vhat) + rc.eps) + rc.weight_decay * decay_mask * master
    return master - rc.learning_rate * upd, m, v


# ---------------------------------------------------------------------------
# ZeRO-1: flat-sharded optimizer state
# ---------------------------------------------------------------------------

def zero1_init_opt(params, dp_world: int):
    """Flat f32 shards (1/W of each tensor) — call inside the shard_map."""
    rank = None  # shards are created from the rank's slice at first step

    def one(p):
        n = _pad_len(p.size, dp_world) // dp_world
        return jnp.zeros((n,), jnp.float32)

    m = jax.tree.map(one, params)
    v = jax.tree.map(one, params)
    return {"m": m, "v": v, "master": None}


def zero1_master_from_params(params, dp_axes):
    """Extract this rank's flat f32 master shard from full params."""
    rank, world = dp_rank_and_world(dp_axes)

    def one(p):
        flat = p.reshape(-1).astype(jnp.float32)
        pad = _pad_len(flat.size, world) - flat.size
        if pad:
            flat = jnp.pad(flat, (0, pad))
        shard = flat.size // world
        return lax.dynamic_slice(flat, (rank * shard,), (shard,))

    return jax.tree.map(one, params)


def zero1_step(params, grads, opt, step, rc: RunConfig, cfg, decay_mask):
    """Full ZeRO-1 step.  grads: full (un-reduced local sums); returns
    (new_params, new_opt).  Collectives: HetCCL AllReduce + AllGather.
    ``cfg``: the program's ``repro.comm.Communicator`` (or a legacy
    ``HetCCLConfig``) — every collective resolves its policy from it.
    ``decay_mask``: per leaf, whether weight decay applies (matrices, not
    norm scales or biases)."""
    with jax.named_scope(SCOPE):
        rank, world = dp_rank_and_world(cfg.dp_axes())
        ef = opt.get("ef")
        if ef is not None:
            grads, ef = ef_apply(grads, ef, ef_codec(rc))
        grads = hetccl.tree_all_reduce(grads, cfg)

        gnorm = global_norm(grads)
        scale = clip_scale(gnorm, rc.grad_clip)

        def one(p, g, m, v, master, decays):
            flat = g.reshape(-1).astype(jnp.float32) * scale
            pad = _pad_len(flat.size, world) - flat.size
            if pad:
                flat = jnp.pad(flat, (0, pad))
            shard = flat.size // world
            g_sh = lax.dynamic_slice(flat, (rank * shard,), (shard,))
            decay = 1.0 if decays else 0.0
            new_master, m, v = adam_update(g_sh, m, v, master, step, rc, decay)
            # parameter AllGather (the ZeRO-1 optimizer-state gather, Table 3)
            full = hetccl.all_gather(new_master.astype(p.dtype), cfg, dim=0)
            full = full[:p.size].reshape(p.shape)
            return full, m, v, new_master

        leaves_p, tdef = jax.tree.flatten(params)
        leaves_g = tdef.flatten_up_to(grads)
        leaves_m = tdef.flatten_up_to(opt["m"])
        leaves_v = tdef.flatten_up_to(opt["v"])
        leaves_ms = tdef.flatten_up_to(opt["master"])
        leaves_d = tdef.flatten_up_to(decay_mask)
        out = [one(*leaf) for leaf in
               zip(leaves_p, leaves_g, leaves_m, leaves_v, leaves_ms, leaves_d)]
        new_p = tdef.unflatten([o[0] for o in out])
        new_opt = {"m": tdef.unflatten([o[1] for o in out]),
                   "v": tdef.unflatten([o[2] for o in out]),
                   "master": tdef.unflatten([o[3] for o in out])}
        if ef is not None:
            new_opt["ef"] = ef
        return new_p, new_opt, gnorm


# ---------------------------------------------------------------------------
# ZeRO-3: shard-shaped optimizer state, cross-pod ring on gradients
# ---------------------------------------------------------------------------

def zero3_init_opt(params):
    """m/v/master in the (already sharded) param shapes."""
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"m": zeros,
            "v": jax.tree.map(jnp.copy, zeros),
            "master": jax.tree.map(lambda p: p.astype(jnp.float32), params)}


def zero3_step(params, grads, opt, step, rc: RunConfig, cfg, fsdp_leaf_mask,
               decay_mask):
    """grads: fsdp leaves already reduce-scattered over 'data' (the
    fsdp_all_gather adjoint); remaining reduction:
      fsdp leaves      -> AllReduce over 'pod' only (HetCCL cross stage),
      replicated leaves-> AllReduce over ('data','pod').
    ``cfg``: communicator (or legacy config); the pod-only projection is a
    ``dataclasses.replace`` like before."""
    with jax.named_scope(SCOPE):
        pod_cfg = dataclasses.replace(cfg, local_axes=())
        ef = opt.get("ef")
        if ef is not None:
            # compensates the pod-stage ring (the fsdp reduce-scatter adjoint
            # quantizes inside autodiff, out of EF's reach — DESIGN.md §17)
            grads, ef = ef_apply(grads, ef, ef_codec(rc))
        def sync(g, is_fsdp):
            if cfg.pod_axis:
                g = hetccl.all_reduce(g, pod_cfg if is_fsdp else cfg)
            elif not is_fsdp:
                g = hetccl.all_reduce(g, cfg)
            return g

        grads = jax.tree.map(sync, grads, fsdp_leaf_mask)
        gnorm = global_norm_sharded(grads, fsdp_leaf_mask, cfg)
        scale = clip_scale(gnorm, rc.grad_clip)

        def one(p, g, m, v, master, decays):
            new_master, m, v = adam_update(g.astype(jnp.float32) * scale, m, v,
                                           master, step, rc, 1.0 if decays else 0.0)
            return new_master.astype(p.dtype), m, v, new_master

        flat = jax.tree.map(one, params, grads, opt["m"], opt["v"], opt["master"],
                            decay_mask)
        new_p = jax.tree.map(lambda o: o[0], flat, is_leaf=lambda x: isinstance(x, tuple))
        new_opt = {"m": jax.tree.map(lambda o: o[1], flat, is_leaf=lambda x: isinstance(x, tuple)),
                   "v": jax.tree.map(lambda o: o[2], flat, is_leaf=lambda x: isinstance(x, tuple)),
                   "master": jax.tree.map(lambda o: o[3], flat, is_leaf=lambda x: isinstance(x, tuple))}
        if ef is not None:
            new_opt["ef"] = ef
        return new_p, new_opt, gnorm


# ---------------------------------------------------------------------------
# Gradient norms / clipping
# ---------------------------------------------------------------------------

def global_norm(tree) -> jax.Array:
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def global_norm_sharded(tree, fsdp_leaf_mask, cfg) -> jax.Array:
    """Norm when fsdp leaves are distinct shards per 'data' rank."""
    sq_sharded = jnp.zeros((), jnp.float32)
    sq_repl = jnp.zeros((), jnp.float32)
    for g, is_fsdp in zip(jax.tree.leaves(tree), jax.tree.leaves(fsdp_leaf_mask)):
        s = jnp.sum(jnp.square(g.astype(jnp.float32)))
        if is_fsdp:
            sq_sharded = sq_sharded + s
        else:
            sq_repl = sq_repl + s
    if cfg.local_axes:
        sq_sharded = lax.psum(sq_sharded, cfg.local_axes)
    return jnp.sqrt(sq_sharded + sq_repl)


def clip_scale(gnorm, max_norm: float):
    if not max_norm:
        return jnp.ones((), jnp.float32)
    return jnp.minimum(1.0, max_norm / (gnorm + 1e-6))
