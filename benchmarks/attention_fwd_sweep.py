"""Block-size sweep of the Pallas attention forward on a TPU.

    PYTHONPATH=src python -m benchmarks.attention_fwd_sweep [--out FILE.json]

At the benchmark cells' attention shapes (causal, bf16: seq 2048 with heads
of 64, GQA 15/5 with 4 rows and 9/3 with 4 and 2 rows; seq 8192 with q and
k 192 wide, v 128, 16 heads and 2 rows), times ``flash_attention_fwd`` for
every (bq, bk) in {128, 256, 512, 1024}^2, and the forward it replaces
(``_old_forward``: every kv block fetched, the mask built on every block,
q, k and v upcast to f32 before both products) at 128 x 128 and at the
blocks the shapes choose, with f32 and with bf16 operands, so the gain
splits into its parts.  A last pair of shapes of equal FLOPs, heads of 64
and of 128, shows what the narrow head costs.

Times are host-clock ms a call, around ``block_until_ready`` of ``N``
back-to-back calls, the least of ``REPS`` means; each is also given as a
share of the bf16 roofline of the FLOPs the causal forward requires.  Each
forward's largest error, relative to the output's largest entry, is taken
against the f32 dense oracle at HIGHEST.

Exits non-zero when JAX finds no TPU.  With ``--out``, writes the whole
sweep there as JSON; the last line of output is its summary.
"""
from __future__ import annotations

import argparse
import functools
import itertools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import compat
from repro.kernels import ref
from repro.kernels.flash_attention import (NEG_INF, _col_to_row,
                                           flash_attention_fwd, fwd_blocks)

# (B, Hq, Hkv, S, d, dv)
SHAPES = {"smollm-360m.b4": (4, 15, 5, 2048, 64, 64),
          "smollm-135m.b4": (4, 9, 3, 2048, 64, 64),
          "smollm-135m.b2": (2, 9, 3, 2048, 64, 64),
          "moonlight.b2": (2, 16, 16, 8192, 192, 128)}
HEAD_PROBE = {"d64": (4, 16, 8, 2048, 64, 64), "d128": (4, 8, 4, 2048, 128, 128)}
BLOCKS = (128, 256, 512, 1024)
N, REPS = 10, 3
BF16_PEAK = 197e12          # TPU v5e, bf16 FLOP/s (Google Cloud, "TPU v5e")


def _ms(fn, *args) -> float:
    jax.block_until_ready(fn(*args))
    means = []
    for _ in range(REPS):
        t = time.perf_counter()
        for _ in range(N):
            out = fn(*args)
        jax.block_until_ready(out)
        means.append((time.perf_counter() - t) / N * 1e3)
    return min(means)


def _old_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, bq, bk, dtype):
    """The replaced forward's body (causal only), its operands cast to
    ``dtype`` (f32 as it was)."""
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    @pl.when((qi + 1) * bq - 1 >= ki * bk)
    def _compute():
        q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(dtype)
        k, v = k_ref[0, 0].astype(dtype), v_ref[0, 0].astype(dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _col_to_row(m_scr[...] + jnp.log(l))


def _old_forward(q, k, v, *, bq, bk, dtype=jnp.float32):
    B, Hq, S, d = q.shape
    Hkv, dv = k.shape[1], v.shape[-1]
    g = Hq // Hkv
    kv_map = lambda b, h, i, j: (b, h // g, j, 0)
    q_map = lambda b, h, i, j: (b, h, i, 0)
    return pl.pallas_call(
        functools.partial(_old_kernel, scale=d ** -0.5, bq=bq, bk=bk, dtype=dtype),
        grid=(B, Hq, S // bq, S // bk),
        in_specs=[pl.BlockSpec((1, 1, bq, d), q_map), pl.BlockSpec((1, 1, bk, d), kv_map),
                  pl.BlockSpec((1, 1, bk, dv), kv_map)],
        out_specs=[pl.BlockSpec((1, 1, bq, dv), q_map),
                   pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, S, dv), q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, 1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32), pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
    )(q, k, v)


def _oracle(q, k, v):
    """f32 causal attention at HIGHEST, one (row, q head) at a time."""
    B, Hq, S, d = q.shape
    g = Hq // k.shape[1]
    flat = lambda x: jnp.repeat(x, g, axis=1).reshape(B * Hq, 1, 1, S, -1)
    with jax.default_matmul_precision("highest"):
        o = jax.lax.map(lambda a: ref.attention(*a), (q.reshape(B * Hq, 1, 1, S, d)
                                                      .astype(jnp.float32),
                                                      flat(k).astype(jnp.float32),
                                                      flat(v).astype(jnp.float32)))
    return o.reshape(B, Hq, S, v.shape[-1])


def _rel_err(got, want) -> float:
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))


def _roofline(shape, ms) -> float:
    """% of the bf16 peak that the FLOPs the causal forward requires take."""
    B, Hq, _, S, d, dv = shape
    return 100 * (2 * B * Hq * S * S * (d + dv) * 0.5 / BF16_PEAK) / (ms * 1e-3)


def _inputs(shape, seed=0):
    B, Hq, Hkv, S, d, dv = shape
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (B, Hq, S, d), jnp.bfloat16),
            jax.random.normal(kk, (B, Hkv, S, d), jnp.bfloat16),
            jax.random.normal(kv, (B, Hkv, S, dv), jnp.bfloat16))


def sweep_shape(shape) -> dict:
    q, k, v = args = _inputs(shape)
    want = jax.jit(_oracle)(*args)
    _, _, _, S, d, dv = shape
    chosen = fwd_blocks(S, S, d, dv, q.dtype.itemsize)
    out: dict = {"chosen": "%dx%d" % chosen, "new": {}, "new_err": {}}

    def run(fn):
        fn = jax.jit(fn)
        return _ms(fn, *args), _rel_err(fn(*args)[0], want)

    for bq, bk in itertools.product(BLOCKS, BLOCKS):
        tag = f"{bq}x{bk}"
        out["new"][tag], out["new_err"][tag] = run(
            lambda q, k, v, bq=bq, bk=bk: flash_attention_fwd(q, k, v, bq=bq, bk=bk))
    for name, (bq, bk), dtype in (("old_f32_128x128", (128, 128), jnp.float32),
                                  ("old_f32_chosen", chosen, jnp.float32),
                                  ("old_bf16_chosen", chosen, jnp.bfloat16)):
        out[name], out[name + "_err"] = run(
            lambda q, k, v, bq=bq, bk=bk, dtype=dtype: _old_forward(
                q, k, v, bq=bq, bk=bk, dtype=dtype))
    out["roofline_pct"] = {tag: _roofline(shape, ms) for tag, ms in out["new"].items()}
    out["old_roofline_pct"] = _roofline(shape, out["old_f32_128x128"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", help="write the whole sweep here as JSON")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's devices are {jax.devices()}", file=sys.stderr)
        return 1
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "shapes": {}, "head_probe_ms": {}}
    for name, shape in SHAPES.items():
        result["shapes"][name] = r = sweep_shape(shape)
        best = min(r["new"], key=r["new"].get)
        print(name, json.dumps({
            "best": [best, round(r["new"][best], 3), round(r["roofline_pct"][best], 2)],
            "chosen": [r["chosen"], round(r["new"][r["chosen"]], 3),
                       round(r["roofline_pct"][r["chosen"]], 2)],
            "old": {k: round(r[k], 3) for k in ("old_f32_128x128", "old_f32_chosen",
                                                 "old_bf16_chosen")},
            "new_128x128": round(r["new"]["128x128"], 3),
            "max_rel_err": max(r["new_err"].values()),
            "old_rel_err": r["old_f32_128x128_err"]}), flush=True)
    for name, shape in HEAD_PROBE.items():
        q, k, v = _inputs(shape)
        result["head_probe_ms"][name] = _ms(jax.jit(flash_attention_fwd), q, k, v)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    summary = {name: {b: round(t, 3) for b, t in r["new"].items()}
               for name, r in result["shapes"].items()}
    print(json.dumps({"device": result["device"], "ms": summary,
                      "head_probe_ms": {k: round(t, 3) for k, t in
                                        result["head_probe_ms"].items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
