"""Plain float32 reference of the DeepSeek-V3 decoder (Moonlight) and its
AdamW training step, written from the published architecture, independent
of the program.

Layer: RMSNorm -> multi-head latent attention -> residual -> RMSNorm ->
feed-forward -> residual; final RMSNorm; LM head; mean token
cross-entropy.

- Attention (MLA, no q LoRA), unabsorbed: ``q = x W_q`` split into q_nope
  and q_pe; ``[c, k_pe] = x W_kv_a``; ``c`` RMS-normed (eps 1e-6); ``[k_nope,
  v] = c W_kv_b``; RoPE (theta from the configuration, split-half rotation)
  on q_pe and on the one-head k_pe, broadcast over the heads; causal
  softmax at scale ``(nope + rope) ** -0.5``; ``o W_o``.  One head at a
  time, recomputed in the backward pass.
- Layer 0: a SwiGLU MLP ``w2(silu(w1 x) * w3 x)``.
- Expert layers: scores ``s = sigmoid(x W_r)`` over all routed experts (the
  router's published width); the top ``k`` of ``s`` (the correction bias
  held at 0) chosen; gates ``s`` at the chosen experts over their sum, times
  the routed scaling factor.  The held experts ``[offset, offset + held)``
  are computed densely, every one on every token, each times its gate,
  which is zero where the expert was not chosen; the shared experts are one
  SwiGLU, added once.  The loss adds DeepSeek-V3's sequence-wise balance
  loss, ``alpha * sum_i f_i P_i`` per sequence over all routed experts.

Optimizer: global-norm gradient clipping, then AdamW with bias correction
and decoupled weight decay on matrices (not on norm scales).

Everything is float32 with every matrix product at ``Precision.HIGHEST``.
``precision="fp8"`` is the control: every matrix product, forward and
backward, takes its operands rounded to float8 e4m3 with a per-tensor scale
(the largest magnitude maps to 448), accumulating in float32.  The batch is
processed in blocks of ``rows`` rows and each layer is recomputed in the
backward pass; between steps the moments wait on the host, so that a
full-size step fits beside nothing else on one chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
KV_NORM_EPS = 1e-6


def _einsum(eqn, a, b):
    return jnp.einsum(eqn, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(eqn, a, b):
    return _einsum(eqn, _fp8(a), _fp8(b))


def _fp8_fwd(eqn, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _einsum(eqn, qa, qb), (qa, qb)


def _fp8_bwd(eqn, res, g):
    _, vjp = jax.vjp(functools.partial(_einsum, eqn), *res)
    return vjp(_fp8(g))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)

MATMULS = {"f32": _einsum, "fp8": _fp8_einsum}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (b, s, heads, d); rotate the two halves of each head."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(h, p, arch, mm):
    b, s, _ = h.shape
    heads, dn = arch["num_attention_heads"], arch["qk_nope_head_dim"]
    r = arch["kv_lora_rank"]
    q = mm("bsd,dhk->bshk", h, p["wq"])
    ckv = mm("bsd,dr->bsr", h, p["wkv_a"])
    c = _rms(ckv[..., :r], p["kv_norm"], KV_NORM_EPS)
    kv = mm("bsr,rhk->bshk", c, p["wkv_b"])
    k_pe = _rope(ckv[..., None, r:], arch["rope_theta"])
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], arch["rope_theta"])], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe, (b, s, heads, k_pe.shape[-1]))], -1)
    v = kv[..., dn:]
    causal = np.tril(np.ones((s, s), bool))
    scale = 1.0 / np.sqrt(q.shape[-1])

    def head(qkv):
        qh, kh, vh = qkv                                   # (b, s, d)
        scores = jnp.where(causal, mm("bqd,btd->bqt", qh, kh) * scale, -jnp.inf)
        return mm("bqt,btd->bqd", jax.nn.softmax(scores, axis=-1), vh)

    o = jax.lax.map(jax.checkpoint(head), tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return mm("hbsk,hkd->bsd", o, p["wo"])


def _swiglu(h, w1, w3, w2, mm):
    return mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", h, w1))
              * mm("bsd,df->bsf", h, w3), w2)


def _experts(h, p, arch, mm):
    """(routed part of the held experts + shared experts, balance loss
    summed over the block's sequences)."""
    e_all, k = arch["n_routed_experts_published"], arch["num_experts_per_tok"]
    off, held = arch["expert_offset"], arch["n_routed_experts"]
    b, s, _ = h.shape
    scores = jax.nn.sigmoid(mm("bsd,de->bse", h, p["router"]))
    _, idx = jax.lax.top_k(scores, k)
    chosen = jax.nn.one_hot(idx, e_all, dtype=jnp.float32).sum(-2)     # (b, s, E)
    sel = scores * chosen
    if arch["norm_topk_prob"]:
        sel = sel / (sel.sum(-1, keepdims=True) + 1e-20)
    gates = sel * arch["routed_scaling_factor"]
    a = (jax.nn.silu(mm("bsd,edf->bsef", h, p["w1"]))
         * mm("bsd,edf->bsef", h, p["w3"]))
    y = mm("bsef,efd->bsed", a, p["w2"])
    out = jnp.einsum("bse,bsed->bsd", gates[..., off:off + held], y, precision=HIGHEST)
    out = out + _swiglu(h, p["shared_w1"], p["shared_w3"], p["shared_w2"], mm)
    f = chosen.sum(1) * (e_all / (k * s))
    probs = (scores / scores.sum(-1, keepdims=True)).mean(1)
    alpha = arch["seq_aux_alpha"] if arch["seq_aux"] else 0.0
    return out, alpha * jnp.sum(f * probs)


def _forward(w, tokens, labels, arch, mm):
    """(sum of token cross-entropies, balance loss summed over the
    sequences and the expert layers) of a block of rows (b, s)."""
    eps = arch["rms_norm_eps"]
    x = jnp.take(w["embed"], tokens, axis=0)

    def dense(x, p):
        x = x + _attention(_rms(x, p["ln1"], eps), p, arch, mm)
        h = _rms(x, p["ln2"], eps)
        return x + _swiglu(h, p["w1"], p["w3"], p["w2"], mm)

    def expert_layer(carry, p):
        x, balance = carry
        x = x + _attention(_rms(x, p["ln1"], eps), p, arch, mm)
        out, bal = _experts(_rms(x, p["ln2"], eps), p, arch, mm)
        return (x + out, balance + bal), None

    def leaves(prefix):
        return {k.split("/", 1)[1]: v for k, v in w.items() if k.startswith(prefix)}

    x = jax.checkpoint(dense)(x, leaves("dense/"))
    (x, balance), _ = jax.lax.scan(jax.checkpoint(expert_layer),
                                   (x, jnp.zeros((), jnp.float32)), leaves("layers/"))
    x = _rms(x, w["final_norm"], eps)
    logits = mm("bsd,dv->bsv", x, w["lm_head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold), balance


def loss_sum(w, tokens, labels, arch, mm):
    """Sum of token cross-entropies of a block of rows (b, s)."""
    return _forward(w, tokens, labels, arch, mm)[0]


def _objective(w, tokens, labels, arch, mm):
    """The trained objective's sum over the block's tokens: the
    cross-entropies, and each sequence's balance loss once per token; and
    the cross-entropy sum beside it."""
    ce, balance = _forward(w, tokens, labels, arch, mm)
    return ce + balance * tokens.shape[-1], ce


@functools.partial(jax.jit, static_argnames=("arch_items", "precision", "rows"))
def _mean_loss_and_grad(w, tokens, labels, *, arch_items, precision, rows):
    """Mean cross-entropy and the mean objective's gradient over all rows,
    ``rows`` rows at a time."""
    arch = dict(arch_items)
    mm = MATMULS[precision]
    t = tokens.reshape(-1, rows, tokens.shape[-1])
    l = labels.reshape(-1, rows, labels.shape[-1])
    grad_fn = jax.value_and_grad(_objective, has_aux=True)

    def block(acc, inp):
        (_, ce), g = grad_fn(w, inp[0], inp[1], arch, mm)
        return (acc[0] + ce, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
    (total, grads), _ = jax.lax.scan(block, zero, (t, l))
    n = tokens.size
    return total / n, jax.tree.map(lambda g: g / n, grads)


def _norms(tree):
    """Per-leaf norms: one per layer of a stacked (``layers/*``) leaf."""
    return {k: (jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1))
                if k.startswith("layers/") else jnp.sqrt(jnp.sum(jnp.square(x)))[None])
            for k, x in tree.items()}


def _decays(name: str) -> bool:
    return not name.endswith(("norm", "/ln1", "/ln2"))


@functools.partial(jax.jit, static_argnames=("opt_items",), donate_argnums=(0, 1, 2, 3))
def _adamw(master, m, v, grads, t, *, opt_items):
    """One AdamW step; returns the new (master, m, v), the pre-clip global
    norm and the per-leaf norms of the clipped gradient."""
    opt = dict(opt_items)
    b1, b2 = opt["beta1"], opt["beta2"]
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = opt["grad_clip"]
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-6)) if clip else jnp.float32(1.0)
    grads = jax.tree.map(lambda g: g * scale, grads)
    new_m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    new_v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)

    def update(name, p, m_, v_):
        mhat = m_ / (1 - b1 ** t)
        vhat = v_ / (1 - b2 ** t)
        decay = opt["weight_decay"] if _decays(name) else 0.0
        return p - opt["learning_rate"] * (mhat / (jnp.sqrt(vhat) + opt["eps"]) + decay * p)

    new = {k: update(k, master[k], new_m[k], new_v[k]) for k in master}
    return new, new_m, new_v, gnorm, _norms(grads)


@jax.jit
def _delta_norms(master, w0):
    return _norms({k: master[k] - w0[k].astype(jnp.float32) for k in master})


@jax.jit
def _param_norms(master, w0):
    """The change of the weights as the next step would compute with them:
    the master weights rounded to the weights' own dtype
    (``reduce_precision``: XLA on a TPU may drop a cast to bfloat16 and
    back, and would read the float32 change)."""
    def rounded(x, dtype):
        f = jnp.finfo(dtype)
        return jax.lax.reduce_precision(x, exponent_bits=f.nexp, mantissa_bits=f.nmant)
    return _norms({k: rounded(master[k], w0[k].dtype) - w0[k].astype(jnp.float32)
                   for k in master})


def _host(tree) -> dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float64) for k, v in jax.device_get(tree).items()}


def train_readings(w0: dict, batches: list[dict], arch: dict, opt: dict, *,
                   precision: str = "f32", rows: int = 1) -> dict:
    """Train from the weights ``w0`` on ``batches`` (one per step, numpy
    ``tokens`` and ``labels`` of any leading shape), and read what the
    comparison needs: each step's mean cross-entropy and pre-clip gradient
    norm, per-leaf norms of the first step's gradient as the optimizer gets
    it (clipped), and per-leaf norms of the weights' change after the last
    step, in float32 (``delta``) and cast to the dtype of ``w0``
    (``param``)."""
    arch_items = tuple(sorted((k, v) for k, v in arch.items()
                              if isinstance(v, (int, float, str))))
    opt_items = tuple(sorted(opt.items()))
    master = {k: jnp.array(v, jnp.float32) for k, v in w0.items()}   # a copy: donated
    moments = None                      # (m, v) on the host between steps
    out = {"loss": [], "gnorm": []}
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, start=1):
            tok = jnp.asarray(batch["tokens"]).reshape(-1, batch["tokens"].shape[-1])
            lab = jnp.asarray(batch["labels"]).reshape(-1, batch["labels"].shape[-1])
            loss, grads = _mean_loss_and_grad(master, tok, lab, arch_items=arch_items,
                                              precision=precision, rows=rows)
            m, v = (jax.tree.map(jnp.zeros_like, (master, master)) if moments is None
                    else jax.device_put(moments))
            master, m, v, gnorm, clipped = _adamw(master, m, v, grads, jnp.float32(t),
                                                  opt_items=opt_items)
            moments = jax.device_get((m, v)) if t < len(batches) else None
            del m, v
            out["loss"].append(float(loss))
            out["gnorm"].append(float(gnorm))
            if t == 1:
                out["grad"] = _host(clipped)
        out["delta"] = _host(_delta_norms(master, w0))
        out["param"] = _host(_param_norms(master, w0))
    return out
