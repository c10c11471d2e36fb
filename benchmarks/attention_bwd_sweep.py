"""Block-size sweep of the Pallas attention backward on a TPU.

    PYTHONPATH=src python -m benchmarks.attention_bwd_sweep [--out FILE.json]

At the benchmark cells' attention shapes (seq 2048, head 64, causal, bf16;
smollm-360m and smollm-135m with 4 rows, the four-chip job's 2 rows a
chip), times each backward kernel (``flash_bwd_dkv``, ``flash_bwd_dq``) for
every (bq, bk) in {256, 512, 1024}^2; the dK/dV kernel also with the GQA
group as per-q-head dK/dV summed outside the kernel (k and v repeated to
the q heads, so the group is one head wide); the whole backward at the
blocks the shapes choose; and the jnp VJP of ``chunked_attention``, the
backward it replaces.  Times are host-clock ms a call, around
``block_until_ready`` of ``N`` back-to-back calls, the least of ``REPS``
means.  It also gives each backward's largest gradient error, relative to
the gradient's largest entry, against the f32 dense oracle at HIGHEST.

Exits non-zero when JAX finds no TPU.  With ``--out``, writes the whole
sweep there as JSON; the last line of output is its summary.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (bwd_blocks, flash_attention_bwd,
                                           flash_attention_fwd,
                                           flash_bwd_dkv, flash_bwd_dq)
from repro.models.attention import chunked_attention, dense_reference

SHAPES = {"smollm-360m.b4": (4, 15, 5), "smollm-135m.b4": (4, 9, 3),
          "smollm-135m.b2": (2, 9, 3)}
SEQ, HEAD = 2048, 64
BLOCKS = (256, 512, 1024)
N, REPS = 10, 3


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


def _rel_err(got, want) -> list[float]:
    return [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip(got, want)]


def sweep_shape(B: int, Hq: int, Hkv: int, seed: int = 0) -> dict:
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, do = (jax.random.normal(kk, (B, Hq, SEQ, HEAD), jnp.bfloat16) for kk in keys[:2])
    k, v = (jax.random.normal(kk, (B, Hkv, SEQ, HEAD), jnp.bfloat16) for kk in keys[2:])
    o, lse = jax.jit(flash_attention_fwd)(q, k, v)
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), -1)[:, :, None, :]
    g = Hq // Hkv
    k_rep, v_rep = (jnp.repeat(x, g, axis=1) for x in (k, v))
    out: dict = {"dkv": {}, "dq": {}, "dkv_per_q_head": {}}
    for bq, bk in itertools.product(BLOCKS, BLOCKS):
        tag = f"{bq}x{bk}"
        blk = dict(bq=bq, bk=bk)
        out["dkv"][tag] = _ms(jax.jit(lambda *a: flash_bwd_dkv(*a, **blk)),
                              q, k, v, do, lse, di)
        out["dq"][tag] = _ms(jax.jit(lambda *a: flash_bwd_dq(*a, **blk)),
                             q, k, v, do, lse, di)

        def per_head(q, k, v, do, lse, di):
            dk, dv = flash_bwd_dkv(q, k, v, do, lse, di, **blk)
            return tuple(x.reshape(B, Hkv, g, SEQ, HEAD).sum(2) for x in (dk, dv))
        out["dkv_per_q_head"][tag] = _ms(jax.jit(per_head), q, k_rep, v_rep, do, lse, di)

    new = jax.jit(flash_attention_bwd)
    out["chosen"] = f"{bwd_blocks(SEQ)}x{bwd_blocks(SEQ)}"
    out["bwd_ms"] = _ms(new, q, k, v, o, lse, do)

    # the replaced backward, in model layout, and the f32 oracle
    ml = [jnp.moveaxis(x, 1, 2) for x in (q, k, v, do)]

    def old(q, k, v, do):
        return jax.vjp(chunked_attention, q, k, v)[1](do)

    def oracle(q, k, v, do):
        with jax.default_matmul_precision("highest"):
            args = [x.astype(jnp.float32) for x in (q, k, v)]
            return jax.vjp(dense_reference, *args)[1](do.astype(jnp.float32))
    out["old_bwd_ms"] = _ms(jax.jit(old), *ml)
    want = jax.jit(oracle)(*ml)
    got_new = [jnp.moveaxis(x, 1, 2) for x in new(q, k, v, o, lse, do)]
    out["rel_err_new"] = _rel_err(got_new, want)
    out["rel_err_old"] = _rel_err(jax.jit(old)(*ml), want)
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
              "shapes": {}}
    for name, shape in SHAPES.items():
        result["shapes"][name] = r = sweep_shape(*shape)
        best = {kern: min(r[kern], key=r[kern].get) for kern in ("dkv", "dq", "dkv_per_q_head")}
        print(name, json.dumps({"best": {k: [b, round(r[k][b], 3)] for k, b in best.items()},
                                "chosen": r["chosen"], "bwd_ms": round(r["bwd_ms"], 3),
                                "old_bwd_ms": round(r["old_bwd_ms"], 3),
                                "rel_err_new": r["rel_err_new"],
                                "rel_err_old": r["rel_err_old"]}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    summary = {name: {k: {b: round(t, 3) for b, t in r[k].items()}
                      for k in ("dkv", "dq", "dkv_per_q_head")}
               for name, r in result["shapes"].items()}
    print(json.dumps({"device": result["device"], "ms": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
