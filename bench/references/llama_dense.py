"""Plain float32 reference of a Llama-style decoder and its AdamW training
step, written from the published architecture, independent of the program.

Block: RMSNorm -> attention (RoPE, split-half rotation; grouped KV heads;
causal softmax) -> residual -> RMSNorm -> SwiGLU MLP ``w2(silu(w1 x) * w3
x)`` -> residual; final RMSNorm; LM head; mean token cross-entropy.
Optimizer: global-norm gradient clipping, then AdamW with bias correction
and decoupled weight decay on matrices (not on norm scales).

Departure from the published models, shared with the program: the LM head
is its own ``(hidden, vocab)`` matrix; the published SmolLM configurations
tie it to the embedding.

Everything is float32 with every matrix product at ``Precision.HIGHEST``.
``precision="fp8"`` is the control: every matrix product, forward and
backward, takes its operands rounded to float8 e4m3 with a per-tensor scale
(the largest magnitude maps to 448), accumulating in float32.  The batch is
processed in blocks of ``rows`` rows and each layer is recomputed in the
backward pass, so that a full-size step fits beside nothing else on one chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


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
    """x: (b, s, heads, hd); rotate the two halves of each head."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def loss_sum(w, tokens, labels, arch, mm):
    """Sum of token cross-entropies of a block of rows (b, s)."""
    eps, theta = arch["rms_norm_eps"], arch["rope_theta"]
    h_q, h_kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    b, s = tokens.shape
    x = jnp.take(w["embed"], tokens, axis=0)
    causal = np.tril(np.ones((s, s), bool))

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        q = _rope(mm("bsd,dhk->bshk", h, p["wq"]), theta)
        k = _rope(mm("bsd,dhk->bshk", h, p["wk"]), theta)
        v = mm("bsd,dhk->bshk", h, p["wv"])
        hd = q.shape[-1]
        q = q.reshape(b, s, h_kv, h_q // h_kv, hd)
        scores = mm("bqkgd,btkd->bkgqt", q, k) / np.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = mm("bkgqt,btkd->bqkgd", probs, v).reshape(b, s, h_q, hd)
        x = x + mm("bshk,hkd->bsd", o, p["wo"])
        h = _rms(x, p["ln2"], eps)
        a = jax.nn.silu(mm("bsd,df->bsf", h, p["w1"])) * mm("bsd,df->bsf", h, p["w3"])
        return x + mm("bsf,fd->bsd", a, p["w2"]), None

    stacked = {k.split("/", 1)[1]: v for k, v in w.items() if k.startswith("layers/")}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)
    x = _rms(x, w["final_norm"], eps)
    logits = mm("bsd,dv->bsv", x, w["lm_head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


@functools.partial(jax.jit, static_argnames=("arch_items", "precision", "rows"))
def _mean_loss_and_grad(w, tokens, labels, *, arch_items, precision, rows):
    """Mean loss and mean gradient over all rows, ``rows`` rows at a time."""
    arch = dict(arch_items)
    mm = MATMULS[precision]
    t = tokens.reshape(-1, rows, tokens.shape[-1])
    l = labels.reshape(-1, rows, labels.shape[-1])
    grad_fn = jax.value_and_grad(loss_sum)

    def block(acc, inp):
        ls, g = grad_fn(w, inp[0], inp[1], arch, mm)
        return (acc[0] + ls, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
    (total, grads), _ = jax.lax.scan(block, zero, (t, l))
    n = tokens.size
    return total / n, jax.tree.map(lambda g: g / n, grads)


@functools.partial(jax.jit, static_argnames=("opt_items",))
def _adamw(master, m, v, grads, t, *, opt_items):
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
        decay = opt["weight_decay"] if "norm" not in name and "/ln" not in name else 0.0
        return p - opt["learning_rate"] * (mhat / (jnp.sqrt(vhat) + opt["eps"]) + decay * p)

    new = {k: update(k, master[k], new_m[k], new_v[k]) for k in master}
    return new, new_m, new_v, grads, gnorm


@jax.jit
def _norms(tree):
    """Per-leaf norms: one per layer of a stacked (``layers/*``) leaf."""
    return {k: (jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1))
                if k.startswith("layers/") else jnp.sqrt(jnp.sum(jnp.square(x)))[None])
            for k, x in tree.items()}


@jax.jit
def _delta_norms(master, w0):
    return _norms({k: master[k] - w0[k].astype(jnp.float32) for k in master})


@jax.jit
def _param_norms(master, w0):
    """The change of the weights as the next step would compute with them:
    the master weights rounded to the weights' own dtype.  The rounding is
    ``reduce_precision``: XLA on a TPU may drop a cast to bfloat16 and back
    (excess precision), and would read the float32 change."""
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
    comparison needs: each step's mean loss and pre-clip gradient norm,
    per-leaf norms of the first step's gradient as the optimizer gets it
    (clipped), and per-leaf norms of the weights' change after the last
    step, in float32 (``delta``) and cast to the dtype of ``w0``
    (``param``)."""
    arch_items = tuple(sorted((k, v) for k, v in arch.items()
                              if isinstance(v, (int, float, str))))
    opt_items = tuple(sorted(opt.items()))
    master = {k: jnp.asarray(v, jnp.float32) for k, v in w0.items()}
    m = jax.tree.map(jnp.zeros_like, master)
    v = jax.tree.map(jnp.zeros_like, master)
    out = {"loss": [], "gnorm": []}
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, start=1):
            tok = jnp.asarray(batch["tokens"]).reshape(-1, batch["tokens"].shape[-1])
            lab = jnp.asarray(batch["labels"]).reshape(-1, batch["labels"].shape[-1])
            loss, grads = _mean_loss_and_grad(master, tok, lab, arch_items=arch_items,
                                              precision=precision, rows=rows)
            master, m, v, clipped, gnorm = _adamw(master, m, v, grads, jnp.float32(t),
                                                  opt_items=opt_items)
            out["loss"].append(float(loss))
            out["gnorm"].append(float(gnorm))
            if t == 1:
                out["grad"] = _host(_norms(clipped))
            del grads, clipped
        out["delta"] = _host(_delta_norms(master, w0))
        out["param"] = _host(_param_norms(master, w0))
    return out
