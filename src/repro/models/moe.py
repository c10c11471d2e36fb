"""Mixture-of-Experts: top-k routing with capacity-buffer dispatch, and
the DeepSeek-V3 expert layer (``deepseek_moe``: sigmoid gate, dropless
ragged dispatch over the experts this chip holds, shared experts).

Dispatch is sort-based: token/expert assignments are sorted by expert id and
scattered into an (E, C, D) capacity buffer — no (T, E, C) one-hot tensor is
ever materialized (memory O(E·C·D) instead of O(T·E·C)).  Tokens overflowing
an expert's capacity are dropped (standard GShard semantics; the router aux
loss keeps overflow rare).

The expert GEMMs run as batched einsums over the capacity buffer on the
reference path; on TPU the TACC registry dispatches to the grouped-matmul
Pallas kernel (`repro.kernels.grouped_matmul`).

Sharding: expert weight tensors are sharded over the 'model' axis on the
expert dim when E divides it (moonshot: 64/16) and on the per-expert FFN dim
otherwise (mixtral: 8 experts, d_ff 14336/16); XLA's SPMD partitioner inserts
the expert-parallel all-to-all when resharding tokens to expert-owning ranks.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import tacc
from repro.kernels.grouped_matmul import ROW_TILE

# Sub-scopes of the DeepSeek-V3 expert layer, under the model's ``mlp``
# scope (DESIGN.md §16): a device trace attributes the routing glue (router,
# dispatch, combine) apart from the expert products.
ROUTER_SCOPE = "moe_router"
DISPATCH_SCOPE = "moe_dispatch"
EXPERTS_SCOPE = "moe_experts"
SHARED_SCOPE = "moe_shared"
COMBINE_SCOPE = "moe_combine"
# Counters of a step (the train step's metrics): rows routed to the held
# experts, summed over layers and micro-steps, and the largest group.
COUNTERS = {"moe_rows_held": "sum", "moe_max_group": "max"}


def _replicated(t):
    """Pin a tensor replicated over the auto (model) axes.

    The token-side dispatch/combine tensors must NOT inherit the expert
    sharding: gathering from an expert-sharded capacity buffer makes XLA
    emit partitioned gathers that all-reduce the top_k-times-expanded
    (T*k, D) matrix (measured 6x wire inflation, EXPERIMENTS.md §Perf).
    Replicating the (E, C, D) buffers costs one (E*C, D) all-gather instead.
    """
    try:
        return jax.lax.with_sharding_constraint(t, P(*([None] * t.ndim)))
    except Exception:
        return t


@tacc.register("expert_ffn", "cpu", default=True)
def expert_ffn_ref(buf, w1, w3, w2):
    """SwiGLU over the capacity buffer.  buf (E,C,D); w* (E,D,F)/(E,F,D).

    The activation stays in the compute dtype: an f32 upcast here makes
    XLA rewrite the dots to f32 and sink the convert through the ZeRO-3
    weight all-gathers, doubling their wire bytes (silu is smooth; bf16 is
    numerically fine and matches the Pallas kernel path)."""
    h1 = jnp.einsum("ecd,edf->ecf", buf, w1)
    h3 = jnp.einsum("ecd,edf->ecf", buf, w3)
    h = jax.nn.silu(h1) * h3
    return jnp.einsum("ecf,efd->ecd", h, w2)


def moe_ffn(x, params, *, n_experts: int, top_k: int, capacity_factor: float,
            router_weight_key: str = "router", expert_axis: str | None = None,
            replicate_buffers: bool = True):
    """x: (T, D) tokens -> (out (T, D), aux_metrics dict).

    params: {"router": (D, E), "w1": (E, D, F), "w3": (E, D, F), "w2": (E, F, D)}
    expert_axis: mesh axis the expert dim is sharded over (None -> per-expert
    FFN-dim TP, the mixtral case).  The expert GEMM output is pinned to that
    sharding before the combine gather — otherwise the SPMD partitioner
    "satisfies" the replication constraint by gathering the weights and
    computing all experts redundantly on every rank (measured on moonshot).
    """
    T, D = x.shape
    E, k = n_experts, top_k
    C = max(int(T * k * capacity_factor / E), 1)

    logits = (x.astype(jnp.float32) @ params[router_weight_key].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)           # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch ------------------------------------------------
    flat_e = expert_idx.reshape(-1)                           # (T*k,)
    order = jnp.argsort(flat_e)                               # stable
    sorted_e = flat_e[order]
    tok_of = order // k
    counts = jnp.bincount(flat_e, length=E)                   # (E,)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - offsets[sorted_e]               # rank within expert
    keep = pos < C
    slot = jnp.where(keep, sorted_e * C + pos, E * C)         # drops -> scratch row

    buf = jnp.zeros((E * C + 1, D), x.dtype)
    buf = buf.at[slot].set(jnp.take(x, tok_of, axis=0), mode="drop")
    buf = buf[:-1].reshape(E, C, D)
    if replicate_buffers:
        buf = _replicated(buf)

    out_buf = tacc.dispatch("expert_ffn", buf, params["w1"], params["w3"],
                            params["w2"])                     # (E, C, D)
    if expert_axis:
        try:
            out_buf = jax.lax.with_sharding_constraint(
                out_buf, P(expert_axis, None, None))
        except Exception:
            pass
    out_flat = (_replicated(out_buf) if replicate_buffers else out_buf).reshape(E * C, D)

    # ---- combine ------------------------------------------------------------
    gathered = jnp.take(out_flat, jnp.minimum(slot, E * C - 1), axis=0)
    gathered = gathered * keep[:, None].astype(gathered.dtype)
    gates_sorted = gate_vals.reshape(-1)[order]
    weighted = gathered * gates_sorted[:, None].astype(gathered.dtype)
    out = jnp.zeros((T, D), jnp.float32).at[tok_of].add(
        weighted.astype(jnp.float32)).astype(x.dtype)

    # ---- aux losses (switch-style load balance + router z-loss) -------------
    me = probs.mean(axis=0)                                   # avg prob per expert
    one_hot_top1 = jax.nn.one_hot(expert_idx[:, 0], E)
    ce = one_hot_top1.mean(axis=0)                            # fraction routed
    aux_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    dropped = 1.0 - keep.mean()
    return out, {"moe_aux": aux_loss, "moe_z": z_loss, "moe_dropped": dropped}


# ---------------------------------------------------------------------------
# DeepSeek-V3 expert layer: sigmoid gate, dropless, a held range of experts
# ---------------------------------------------------------------------------

def reduce_counters(per: dict) -> dict:
    """Counters over their leading axis (layers or micro-steps), summed or
    the largest, as ``COUNTERS`` says."""
    return {k: v.sum(0) if COUNTERS[k] == "sum" else v.max(0)
            for k, v in per.items()}


def sigmoid_gate(x, router, *, top_k: int, routed_scaling: float,
                 norm_topk: bool):
    """DeepSeek-V3's gate over all experts: scores ``s = sigmoid(x W_r)`` in
    f32 (T, E); the top ``top_k`` of ``s + b`` chosen, with the correction
    bias ``b`` held at 0; gates ``s`` at the chosen experts, over their sum
    (``norm_topk``), times ``routed_scaling``.  Returns (s, idx (T, k),
    gates (T, k) f32)."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s, top_k)
    gates = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return s, idx, gates * routed_scaling


def seq_balance_loss(s, idx, n_seqs: int, alpha: float):
    """DeepSeek-V3's sequence-wise balance loss (eqs. 17-20), averaged over
    the ``n_seqs`` sequences of ``s`` (T, E): ``alpha * sum_i f_i P_i``, with
    ``f_i = E / (k S) * #(tokens choosing i)`` and ``P_i`` the mean over the
    sequence of ``s_i / sum_j s_j``."""
    T, E = s.shape
    k, S = idx.shape[-1], T // n_seqs
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(-2)
    f = chosen.reshape(n_seqs, S, E).sum(1) * (E / (k * S))
    p = (s / s.sum(-1, keepdims=True)).reshape(n_seqs, S, E).mean(1)
    return alpha * jnp.mean(jnp.sum(f * p, axis=-1))


def dispatch_plan(idx, *, offset: int, held: int, tm: int = ROW_TILE):
    """Where each (token, expert) pair goes in the experts' buffer.

    The pairs whose expert is in ``[offset, offset + held)`` are sorted by
    expert (a stable counting sort) into a buffer of worst-case rows,
    ``T * min(k, held)`` plus each group's padding to a multiple of ``tm``;
    every group starts on a multiple of ``tm``.  Returns ``dest`` (T*k,):
    each pair's row, ``R`` (out of range) for an expert held elsewhere;
    ``row_token`` (R,): each row's token, ``T`` for an empty row; ``sizes``
    (held,) the pairs of each held expert; ``padded`` (held,) the sizes
    padded to ``tm``."""
    T, k = idx.shape
    n_rows = T * min(k, held) + held * (tm - 1)
    R = -(-n_rows // tm) * tm
    local = (idx - offset).reshape(-1)
    e = jnp.where((local >= 0) & (local < held), local, held)
    onehot = jax.nn.one_hot(e, held + 1, dtype=jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), e[:, None], axis=1)[:, 0] - 1
    sizes = onehot.sum(0)[:held]
    padded = -(-sizes // tm) * tm
    starts = jnp.cumsum(padded) - padded
    dest = jnp.where(e < held, starts[jnp.minimum(e, held - 1)] + rank, R)
    row_token = jnp.full((R,), T, jnp.int32).at[dest].set(
        jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop")
    return dest, row_token, sizes, padded


def _swiglu(x, w1, w3, w2):
    h = jax.nn.silu(jnp.dot(x, w1.astype(x.dtype)).astype(jnp.float32)).astype(x.dtype)
    return jnp.dot(h * jnp.dot(x, w3.astype(x.dtype)), w2.astype(x.dtype))


def deepseek_moe(x, p, *, n_seqs: int, top_k: int,
                 expert_offset: int, n_held: int, routed_scaling: float,
                 norm_topk: bool, seq_aux_alpha: float):
    """x: (T, D) tokens, ``n_seqs`` sequences one after another -> (out (T,
    D), aux).

    The router (p["router"] (D, E)) scores all E experts; this layer holds
    experts ``[expert_offset, expert_offset + n_held)`` (p["w1"], p["w3"]
    (held, D, F), p["w2"] (held, F, D)) and computes only their part of
    the routed output, ``sum g * SwiGLU_e(x)`` over the chosen experts it
    holds; no token is dropped.  The shared experts (p["shared"], one
    SwiGLU) are added once.  aux: ``moe_balance`` (the weighted balance
    loss) and the ``COUNTERS``."""
    T, D = x.shape
    with jax.named_scope(ROUTER_SCOPE):
        s, idx, gates = sigmoid_gate(x, p["router"], top_k=top_k,
                                     routed_scaling=routed_scaling,
                                     norm_topk=norm_topk)
        balance = (seq_balance_loss(s, idx, n_seqs, seq_aux_alpha)
                   if seq_aux_alpha else jnp.zeros((), jnp.float32))
    with jax.named_scope(DISPATCH_SCOPE):
        dest, row_token, sizes, padded = dispatch_plan(
            idx, offset=expert_offset, held=n_held)
        buf = jnp.take(x, row_token, axis=0, mode="fill", fill_value=0)
        gate_row = jnp.zeros(row_token.shape, jnp.float32).at[dest].set(
            gates.reshape(-1), mode="drop")
    with jax.named_scope(EXPERTS_SCOPE):
        h1 = tacc.dispatch("ragged_grouped_matmul", buf, p["w1"].astype(x.dtype), padded)
        h3 = tacc.dispatch("ragged_grouped_matmul", buf, p["w3"].astype(x.dtype), padded)
        h = jax.nn.silu(h1.astype(jnp.float32)).astype(x.dtype) * h3
        y = tacc.dispatch("ragged_grouped_matmul", h, p["w2"].astype(x.dtype), padded)
    with jax.named_scope(COMBINE_SCOPE):
        weighted = y * gate_row[:, None].astype(y.dtype)
        out = jnp.zeros((T, D), jnp.float32).at[row_token].add(
            weighted.astype(jnp.float32), mode="drop")
    if "shared" in p:
        with jax.named_scope(SHARED_SCOPE):
            sh = p["shared"]
            out = out + _swiglu(x, sh["w1"], sh["w3"], sh["w2"]).astype(jnp.float32)
    aux = {"moe_balance": balance, "moe_rows_held": sizes.sum(),
           "moe_max_group": sizes.max()}
    return out.astype(x.dtype), aux
